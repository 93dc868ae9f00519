"""Share of the router's picks that were of zero-compute (identity) experts,
over the traced slice's matched dispatches: ``moe_zero_picks`` over
``moe_picks`` of ``engine/dispatch``, which the step programs count themselves
and hand back behind the picked tokens (a span carries the counts of the steps
reconciled before it, every step's once: ``ragged._counts_attr``). A property
of the weights and the traffic, not of the program's speed: ~256 / 768 with
seeded weights; a PR that moves it has changed the routing. A program that
writes no such argument (every family without zero-compute experts, a parent
commit) gives no value."""
import host_spans


def read(ctx):
    tl = host_spans.timeline(ctx)
    pairs = host_spans.matched(tl) if tl else None
    args = [a for a, _, _ in pairs or () if "moe_picks" in a]
    picks = sum(a["moe_picks"] for a in args)
    return 100.0 * sum(a["moe_zero_picks"] for a in args) / picks if picks else None
