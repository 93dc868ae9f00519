"""Rows a held expert gets in a step, mean over the traced slice's matched
dispatches: ``moe_held_picks`` of ``engine/dispatch`` (the picks that landed on
an expert this rank holds, counted by the step programs and handed back behind
the picked tokens) over the held experts of all layers (the reference's
``held_expert_slots``: 16 x 4) and over the dispatches. ~8 in a 512-row step
of one chip's own tokens, where the deployment's exchange would bring ~256:
the number the configuration's ``reduced_why`` quotes. A program that writes no
such argument, or a reference without the count, gives no value."""
import host_spans


def read(ctx):
    slots = getattr(ctx["reference"], "held_expert_slots", None)
    tl = host_spans.timeline(ctx)
    pairs = host_spans.matched(tl) if tl else None
    args = [a for a, _, _ in pairs or () if "moe_held_picks" in a]
    if not slots or not args:
        return None
    return (sum(a["moe_held_picks"] for a in args)
            / (slots(ctx["cfg"]) * len(args)))
