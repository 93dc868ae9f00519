"""Share of the traced slice in which no operation ran on the device
(1 - union of operation intervals / slice), averaged over the chips."""


def read(ctx):
    trace = ctx["window"]["trace"]
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"]) if trace else None
