"""Planted faults for a serving cell's ``correct``: each has to come out as
NOT correct through ``serve_cell.ServeRig.check`` itself, and the served path
as correct. On the chip, one JSON line a seed:

    python benchmark/check_controls.py --workload <cell> --seed <n> [--seed <m> ..]

The weights are the cell's (``--seed``), the engine the cell's, the requests
the first ``serve_cell.CHECK_SAMPLE`` of the seed's streams whose prompt is at
least a window long where the model has one (the controls are about rows that
have slid; a shorter request runs the same code under the window). They are
served through the engine alone (no HTTP, no load) and compared three times,
by the harness's own comparison and limits:

- ``served``: against the reference as it is. Has to be correct.
- ``lower``: against the reference computed in the nearest precision below
  the configuration's in which it stays finite (``float8_e5m2`` under
  bfloat16) where the check asks for float32. A float32 reference and a
  float8 program would read the same disagreement; autoregressive float8
  decoding of 8K contexts is not affordable. The gap's limit is 4 x |bf16 -
  reference| and so reads the float8 error itself here: the match rate
  decides.
- ``edge`` (a model with ``sliding_window``): against the reference with the
  window one block shorter, the fault a kernel's first block, a mask's edge
  or a block handed back one step early would make.

Exit code 1 if ``served`` is not correct or a control is.
"""

from __future__ import annotations

import argparse
import dataclasses
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
import serve_cell  # noqa: E402
import trafficgen  # noqa: E402

STREAMS = 16
LOWER = {"bfloat16": "float8_e5m2"}


def requests(spec: dict, seed: int, at_least: int) -> list:
    """The first ``CHECK_SAMPLE`` requests of the seed's streams (stream by
    stream, then the next index) whose prompt has ``at_least`` tokens."""
    out = []
    for index in range(64):
        for stream in range(STREAMS):
            r = trafficgen.request(spec["mix"], seed, stream, index)
            if r["prompt_len"] >= at_least:
                out.append(r)
            if len(out) == serve_cell.CHECK_SAMPLE:
                return out
    raise SystemExit(f"no request of {at_least} prompt tokens in the mix")


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
    window = getattr(cfg, "sliding_window", None)
    records = requests(spec, seed, window or 0)
    for uid, r in enumerate(records):
        engine.put(uid, trafficgen.prompt_tokens(
            seed, r["stream_id"], r["i"], r["prompt_len"], cfg.vocab_size),
            max_new_tokens=r["max_tokens"])
    served = engine.generate_all()
    records = [{**r, "status": 200, "tokens": list(served[uid])}
               for uid, r in enumerate(records)]

    def check(ref) -> dict:
        rig = types.SimpleNamespace(engine=engine, seed=seed, cfg=cfg,
                                    spec=spec, reference=ref)
        return serve_cell.ServeRig.check(rig, records)

    def forward_as(cfg_seen=cfg, lower=None):
        def forward(_, p, ids, dt):
            if lower is not None and dt == jnp.float32:
                dt = lower
            return reference.forward(cfg_seen, p, ids, dt)
        return types.SimpleNamespace(forward=forward)

    out = {"seed": seed, "prompt_lens": [r["prompt_len"] for r in records],
           "distinct_served_tokens": len({t for r in records
                                          for t in r["tokens"]}),
           "served": check(reference),
           "lower": check(forward_as(
               lower=getattr(jnp, LOWER[serve["dtype"]])))}
    if window:
        out["edge"] = check(forward_as(dataclasses.replace(
            cfg, sliding_window=window - engine.cfg.block_size)))
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
        planted = [k for k in ("lower", "edge") if k in out]
        out["as_it_should"] = bool(out["served"]["ok"]) and not any(
            out[k]["ok"] for k in planted)
        out["seconds"] = round(time.perf_counter() - t0, 1)
        bad += not out["as_it_should"]
        print(json.dumps(out), flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    raise SystemExit(main())
