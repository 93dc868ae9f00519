"""Device time of the KDA chunk kernel (``kernels/kda_chunk.json``: the delta
rule's chunk form over a step's prefill tiles, a head and a tile a grid step)
over device busy time in the traced slice."""
import host_spans


def read(ctx):
    return host_spans.kernel_share(ctx, "kda_chunk")
