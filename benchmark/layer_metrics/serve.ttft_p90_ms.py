"""Time to the first token, 90th percentile over the window's attempted
requests: the backlog behind a burst. Not judged: it sits between the
requests that queued and those that did not, and spread 42% in the driver's
runs of PR 22."""
import reduce


def read(ctx):
    win = ctx["window"]
    tried = reduce.attempted(win["records"], win["seconds"], True)
    return reduce.latency_metric("ttft_p90_ms", tried,
                                 reduce.missing_ttft_ms(
                                     win["seconds"], ctx["spec"]["mix"]))
