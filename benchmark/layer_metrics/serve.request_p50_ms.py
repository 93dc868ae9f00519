"""Median request latency (sent -> whole answer) of requests sent in the
window, at the client."""
import reduce


def read(ctx):
    win = ctx["window"]
    ms = reduce.request_ms(reduce.attempted(win["records"], win["seconds"],
                                            win["open_loop"]))
    return reduce.percentile(ms, 50) if ms else None
