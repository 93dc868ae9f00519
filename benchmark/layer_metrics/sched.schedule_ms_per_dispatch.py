"""Host time in ``engine/schedule`` (admission, SplitFuse packing, block
allocation; the row updates nested inside it are ``engine/stage``'s) per
``engine/dispatch`` span of the traced slice: the part of
``sched.host_ms_per_dispatch`` that is scheduling."""
import host_spans


def read(ctx):
    tl = host_spans.timeline(ctx)
    n = len(host_spans.spans(tl, "engine/dispatch")) if tl else 0
    return host_spans.self_seconds(tl, "engine/schedule") * 1e3 / n if n else None
