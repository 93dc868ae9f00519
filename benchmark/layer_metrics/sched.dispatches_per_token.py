"""Step programs dispatched per token emitted in the window
(``dispatch_count`` / ``tokens_emitted`` of the ragged engine): about
1 / mean decode batch while decode is one dispatch per token."""


def read(ctx):
    c = ctx["window"]["counters"]
    return c["dispatch_count"] / c["tokens_emitted"] if c.get("tokens_emitted") else None
