"""Device time of the Pallas attention kernels of the serving path (tiled
prefill and paged decode together: ``kernels/pallas_custom_call.json`` says
why they cannot be told apart yet) over device busy time in the traced slice."""
import reduce


def read(ctx):
    return reduce.kernel_share(ctx, "pallas_custom_call")
