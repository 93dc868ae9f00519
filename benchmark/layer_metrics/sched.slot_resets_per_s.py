"""Slots started from zeros a second of the traced slice: ``slot_resets`` of
its ``engine/dispatch`` spans (``ragged._state_attr``: the sequences whose
first tile a step carries, so the arrivals, and a preempted request run again;
the slot is zeroed inside the step program, there is no reset program) over
the device's slice (``host_spans.slice_s``). A program that writes no such
argument, or no span, gives no value."""
import host_spans


def read(ctx):
    tl = host_spans.timeline(ctx)
    resets = [args["slot_resets"]
              for _, _, args in (host_spans.spans(tl, "engine/dispatch") if tl else ())
              if "slot_resets" in args]
    return sum(resets) / host_spans.slice_s(tl) if resets else None
