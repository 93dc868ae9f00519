"""Front end's submit -> admission into an engine slot, mean over the
requests admitted in the traced slice (``wait_s`` of the ``request/admit``
instants)."""
import host_spans


def read(ctx):
    return host_spans.mean_wait_ms(ctx, "request/admit")
