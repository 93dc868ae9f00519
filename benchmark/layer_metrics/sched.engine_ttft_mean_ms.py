"""Front end's submit -> first token read back by the engine, mean over the
requests whose first token fell in the traced slice (``wait_s`` of the
``request/first_token`` instants). ``serve.ttft_p50_ms`` less this is the
front end's and the transport's part of a first token."""
import host_spans


def read(ctx):
    return host_spans.mean_wait_ms(ctx, "request/first_token")
