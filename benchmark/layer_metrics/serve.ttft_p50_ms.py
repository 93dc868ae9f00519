"""Time to the first token (due -> first SSE token frame at the client),
median over the window's attempted requests. Not judged: it rides on where
each arrival falls inside a running step and on the host's threads, and moved
8% beside thirteen busy processes (PR 22)."""
import reduce


def read(ctx):
    win = ctx["window"]
    tried = reduce.attempted(win["records"], win["seconds"], True)
    return reduce.latency_metric("ttft_p50_ms", tried,
                                 reduce.missing_ttft_ms(
                                     win["seconds"], ctx["spec"]["mix"]))
