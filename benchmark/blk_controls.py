"""Planted faults for the ``correct`` of a cell whose model generates by
diffusion over blocks: each has to come out as NOT correct through
``serve_cell.ServeRig.check`` itself, and the served path as correct. On the
chip, one JSON line a seed:

    python benchmark/blk_controls.py --workload <cell> --seed <n> [--seed <m> ..]

As ``check_controls.py`` (which is for every serving cell and is not edited):
the weights are the cell's, the engine the cell's, the requests the first
``serve_cell.CHECK_SAMPLE`` of the seed's streams, served through the engine
alone and compared by the harness's own comparison and limits, against

- ``served``: the reference as it is. Has to be correct.
- ``plain``: the reference in the configuration's own precision (bfloat16)
  where the check asks for float32: what a plain forward pass of the stated
  precision agrees to. Recorded, not judged.
- ``lower``: the reference in the nearest precision below the configuration's
  in which it stays finite (``float8_e5m2`` under bfloat16).
- ``causal_in_block``: the reference with a CAUSAL mask inside a block (a row
  sees the rows of its block at or before it) where the model's is two-way:
  the fault a decode kernel that kept ``kpos <= qpos``, or a tile mask without
  ``| (B - 1)``, would make.
- ``no_commit``: the reference whose later blocks read each block as it stood
  in its LAST DENOISE pass (half masked at ``T = 2``): the commit pass left
  out, the pool keeping the half-masked block's K and V.
- ``shifted``: the reference's logits read the autoregressive way (row ``i -
  1`` of the unshifted array for token ``i``): a program that took the
  row before a position for its pick.
- ``other_T``: the replay at ``T = 4`` passes a block where the cell ran its
  configuration's: a pass index or a count a pass that is off.

Exit code 1 if ``served`` is not correct or a control is.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
import types

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))

import cellspec  # noqa: E402
import check_controls  # noqa: E402
import serve_cell  # noqa: E402
import trafficgen  # noqa: E402

PLANTED = ("lower", "causal_in_block", "no_commit", "shifted", "other_T")


def references(reference, serve_dtype: str) -> dict:
    """``{name: a stand-in for the reference module}`` of ``served`` and the
    planted faults; each gives ``forward(cfg, params, ids, dtype)`` in the
    harness's convention (row ``i - 1`` for token ``i``)."""
    import jax.numpy as jnp

    lower = getattr(jnp, check_controls.LOWER[serve_dtype])
    plain = getattr(jnp, serve_dtype)

    def shifted(logits):
        return jnp.concatenate([logits[1:], jnp.zeros_like(logits[:1])])

    def forward_with(**fault):
        return lambda cfg, p, ids, dt: shifted(
            reference.denoise_logits(cfg, p, ids, dt, **fault))

    return {name: types.SimpleNamespace(forward=fn) for name, fn in {
        "served": reference.forward,
        "plain": lambda cfg, p, ids, dt: reference.forward(cfg, p, ids, plain),
        "lower": lambda cfg, p, ids, dt: reference.forward(
            cfg, p, ids, lower if dt == jnp.float32 else dt),
        "causal_in_block": forward_with(in_block=lambda i, j: j <= i),
        "no_commit": forward_with(commit=False),
        # row i - 1 of the UNSHIFTED array for token i
        "shifted": reference.denoise_logits,
        "other_T": forward_with(steps=4),
    }.items()}


def controls(spec: dict, seed: int) -> dict:
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.inference.ragged import (RaggedConfig,
                                                RaggedInferenceEngine)

    family, cfg, reference = cellspec.model(spec)
    serve = spec["config"]["serve"]
    dtype = getattr(jnp, serve["dtype"])
    params = jax.jit(lambda key: jax.tree_util.tree_map(
        lambda x: x.astype(dtype), family.init_params(cfg, key)))(
            jax.random.PRNGKey(seed))
    engine = RaggedInferenceEngine(
        lambda ctx: family.build(cfg, ctx=ctx),
        RaggedConfig(**{**serve["engine"], **spec["cell"].get("engine", {})}),
        dtype=dtype, params=params, seed=seed)
    records = check_controls.requests(spec, seed, 0)
    for uid, r in enumerate(records):
        engine.put(uid, trafficgen.prompt_tokens(
            seed, r["stream_id"], r["i"], r["prompt_len"], cfg.vocab_size),
            max_new_tokens=r["max_tokens"])
    served = engine.generate_all()
    records = [{**r, "status": 200, "tokens": list(served[uid])}
               for uid, r in enumerate(records)]
    blk = cfg.block_length
    blocks = [r["tokens"][i:i + blk] for r in records
              for i in range(0, len(r["tokens"]) - blk + 1, blk)]
    out = {"seed": seed, "generated": [len(r["tokens"]) for r in records],
           "distinct_served_tokens": len({t for r in records
                                          for t in r["tokens"]}),
           # a block of identical MASK rows must not decode to one token at
           # every offset: then no pick could see the mask or the passes
           "one_token_blocks_share": sum(len(set(b)) == 1 for b in blocks)
           / max(1, len(blocks))}
    for name, ref in references(reference, serve["dtype"]).items():
        rig = types.SimpleNamespace(engine=engine, seed=seed, cfg=cfg,
                                    spec=spec, reference=ref)
        out[name] = serve_cell.ServeRig.check(rig, records)
    engine.params = None
    del engine, params
    gc.collect()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, action="append", required=True)
    args = ap.parse_args(argv)
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    spec = cellspec.resolve(args.workload)
    bad = 0
    for seed in args.seed:
        t0 = time.perf_counter()
        out = controls(spec, seed)
        out["as_it_should"] = bool(out["served"]["ok"]) and not any(
            out[k]["ok"] for k in PLANTED)
        out["seconds"] = round(time.perf_counter() - t0, 1)
        bad += not out["as_it_should"]
        print(json.dumps(out), flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    raise SystemExit(main())
