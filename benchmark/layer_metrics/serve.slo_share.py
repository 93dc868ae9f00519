"""Share of attempted requests that met both latency limits users feel
(the mix's ``limits``: first token within ``ttft_ms`` of being due, no gap
between tokens over ``gap_ms``). Recorded, not judged: it does not define the
knee."""
import reduce


def read(ctx):
    win, limits = ctx["window"], ctx["spec"]["mix"]["limits"]
    tried = reduce.attempted(win["records"], win["seconds"], True)
    if not tried:
        return None
    return 100.0 * reduce.slo_share(tried, limits["ttft_ms"], limits["gap_ms"])
