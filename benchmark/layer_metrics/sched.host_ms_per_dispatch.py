"""Host time staging one dispatch (``host_stage_ns`` / ``dispatch_count``):
packing, upload and enqueue, not device execution."""


def read(ctx):
    c = ctx["window"]["counters"]
    return c["host_stage_ns"] / c["dispatch_count"] / 1e6 if c.get("dispatch_count") else None
