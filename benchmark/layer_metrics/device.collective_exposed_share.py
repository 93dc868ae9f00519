"""Share of the traced slice spent in collective operations that no compute
covered (four-chip cells)."""


def read(ctx):
    trace = ctx["window"]["trace"]
    if not trace or ctx["chips"] < 2:
        return None
    return 100.0 * trace["collective_exposed_s"] / trace["window_s"]
