"""Of the rows the matched ``engine/dispatch`` spans scheduled (``tokens``),
those the pool's write site took as slices, a prefill tile's rows whole blocks
at a time (``pool_slice_rows``, PR 48), per cent; the rest went into the pool
as single rows of one scatter. A property of the traffic (its share of prompt
rows), which says how much of a step's cache write the slice form reaches.
Nothing where the spans carry no such count (a program from before PR 48)."""
import latent_spans


def read(ctx) -> float | None:
    _, pairs = latent_spans._matched(ctx)
    rows = [a for a, _, _ in pairs or () if "pool_slice_rows" in a]
    tokens = sum(a["tokens"] for a in rows)
    if not tokens:
        return None
    return 100.0 * sum(a["pool_slice_rows"] for a in rows) / tokens
