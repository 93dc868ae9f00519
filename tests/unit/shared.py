"""What the cases of one test module share, because a test at these sizes is
XLA compile time (seconds a program, against milliseconds a step).

``one_engine_each``: one ``RaggedInferenceEngine`` for the cases that ask for
the same options. The step programs are the engine's own, so two cases that
differ in the requests they send compile them once. A case that reads an
engine's running counts, degrades it, patches what it traces, or is about its
first compile builds its own.

``over_one_length``: a plain reference that runs a sequence operation by
operation compiles each of them again for every new length; padded to one
length it compiles once."""

import jax.numpy as jnp
import numpy as np


def one_engine_each(build):
    """``get(*args, second=False, **options)``: ``build(*args, **options)``
    the first time those arguments are asked for and that engine again after,
    each time with every sequence retired and its pools, tables and slot rows
    as a new engine has them (``reset_state``, the engine's own containment:
    compiled programs and parameters stay). ``second=True`` is another engine
    of the same arguments, for a case that holds two at once."""
    built = {}

    def get(*args, second=False, **options):
        key = (args, second, tuple(sorted(options.items())))
        if key not in built:
            built[key] = build(*args, **options)
        built[key].reset_state()
        return built[key]

    return get


def over_one_length(forward, rows):
    """``run(cfg, params, ids)``: ``forward(cfg, params, ids)`` of a causal
    reference that routes a token by itself, run over ``rows`` positions
    (``ids`` and zeros after them) and cut back to ``ids``: rows after the
    sequence change none of the sequence's. The first call holds that to
    1e-6 against the reference run on ``ids`` alone."""
    checked = []

    def run(cfg, params, ids):
        ids = tuple(ids)
        got = np.asarray(forward(cfg, params, jnp.asarray(
            ids + (0,) * (rows - len(ids)))))[:len(ids)]
        if not checked:
            np.testing.assert_allclose(got, np.asarray(
                forward(cfg, params, jnp.asarray(ids))), atol=1e-6)
            checked.append(True)
        return got

    return run
