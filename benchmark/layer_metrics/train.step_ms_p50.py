"""Median step time in the window: host clock around a fetched loss."""
import reduce


def read(ctx):
    steps = ctx["window"].get("step_s")
    return reduce.percentile(steps, 50) * 1e3 if steps else None
