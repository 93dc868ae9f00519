"""Rows of the decode bucket that were padding: ``state_pad_rows`` of the
traced slice's ``engine/dispatch`` spans (``ragged._state_attr``: the rows of
the program's decode bucket past its real ones; they name the scratch slot and
the state-update kernel moves its state like any row's) over the decode rows
the programs executed (the ``d<rows>`` of their names, as ``host_spans.matched``
reads it), per cent. A family with slot state has ONE decode bucket
(``ModelSpec.decode_bucket_min``), so under an open loop this is how far the
live rows fall short of it. A program that writes no such argument (a family
without slot state, a parent commit), or no span, gives no value."""
import re

import host_spans


def read(ctx):
    tl = host_spans.timeline(ctx)
    pad = rows = 0
    for _, _, args in host_spans.spans(tl, "engine/dispatch") if tl else ():
        key = re.match(r"ragged_step_d(\d+)_t\d+$", args.get("program", ""))
        if key and "state_pad_rows" in args:
            pad += args["state_pad_rows"]
            rows += int(key.group(1))
    return 100.0 * pad / rows if rows else None
