"""Share of the engine loop thread's time under ``loop/deliver`` spans (handing
the step's tokens to the requests' consumers, then the router's snapshot) over
the thread's extent in the traced slice: what of the loop's turn the serving
tier takes from the engine. Near 0 the turn is the engine's own."""
import host_spans


def read(ctx):
    tl = host_spans.timeline(ctx)
    line = host_spans.driver_thread(tl) if tl else None
    if not line:
        return None
    spent = sum(d for n, _, d, _ in line["events"] if n == "loop/deliver")
    return 100.0 * spent * 1e-9 / host_spans.thread_extent_s(line)
