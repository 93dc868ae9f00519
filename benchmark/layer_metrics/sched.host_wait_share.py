"""Share of the engine loop thread's time spent in ``engine/readback`` (the
blocking fetch of the picked tokens) over the thread's extent in the traced
slice: near 100% the device sets the pace and the host waits for it."""
import host_spans


def read(ctx):
    tl = host_spans.timeline(ctx)
    line = host_spans.driver_thread(tl) if tl else None
    if not line:
        return None
    waited = sum(d for n, _, d, _ in line["events"] if n == "engine/readback")
    return 100.0 * waited * 1e-9 / host_spans.thread_extent_s(line)
