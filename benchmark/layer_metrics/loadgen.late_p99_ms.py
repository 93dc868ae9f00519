"""How late the load generator sent requests (sent - due), 99th percentile.
Small against the time to first token (``serve.ttft_p50_ms``) or that number
measures the generator."""
import reduce


def read(ctx):
    win = ctx["window"]
    late = reduce.late_ms(reduce.attempted(win["records"], win["seconds"], True))
    return reduce.percentile(late, 99) if late else None
