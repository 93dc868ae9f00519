"""Share of the token rows the step programs computed that were padding
(``tokens_padded`` / (``tokens_scheduled`` + ``tokens_padded``))."""


def read(ctx):
    c = ctx["window"]["counters"]
    rows = c.get("tokens_scheduled", 0) + c.get("tokens_padded", 0)
    return 100.0 * c["tokens_padded"] / rows if rows else None
