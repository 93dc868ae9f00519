"""Model FLOP/s utilization: FLOPs the forward and backward passes require
per token (``reference/<family>.py``: 6 N + attention; recomputation does not
count) x tokens/s over chips x the published bf16 peak."""


def read(ctx):
    win = ctx["window"]
    rate = ctx["end_to_end"].get("train_tokens_per_s")
    if rate is None:
        return None
    flops = ctx["reference"].train_flops_per_token(ctx["cfg"], win["seq_len"])
    return 100.0 * flops * rate / (ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"])
