"""How much of a step's least traffic the recurrent state is: ``state_bytes``
of the traced slice's matched dispatches (``engine/dispatch``: decode rows +
distinct prefilling slots, a slot's state once each way) over ``state_bytes`` +
dispatches x the reference's ``weight_bytes`` + its ``kv_bytes_per_token`` x
``kv_tokens``, per cent. A program that writes no such argument (a family
without slot state, a parent commit), or no span, gives no value."""
import latent_spans
import ssm_spans


def read(ctx):
    geo, pairs = latent_spans.geometry(ctx), ssm_spans._state_pairs(ctx)
    if not geo or not pairs:
        return None
    state = sum(a["state_bytes"] for a, _, _ in pairs)
    rest = (len(pairs) * ctx["reference"].weight_bytes(ctx["cfg"])
            + geo["kv_bytes_per_token"] * sum(a["kv_tokens"] for a, _, _ in pairs))
    return 100.0 * state / (state + rest)
