"""Resolve a cell by name: everything the harness knows comes from data files.

``BENCHMARK.json`` names the cell's configuration and traffic mix; the files
are found by those names under this directory:

- ``configs/<config>.json``     the model's published keys, ``family``, the
                                map from the program's config dataclass fields
                                to those keys, and the deployment's sizes
- ``traffic/<traffic>.json``    the mix: a ``kind`` and its parameters
- ``cells/<cell>.json``         what belongs to the pair: the fixed rate or
                                client count, engine-size overrides, the
                                training loss band (optional file)
- ``reference/<family>.py``     the plain reference and the model arithmetic
- ``layer_metrics/<name>.py``   one reader per per-layer metric
- ``kernels/<name>.json``       one kernel: trace-name patterns, ops and bytes

A later PR adds files and ``BENCHMARK.json`` entries; nothing here names a
model, a mix or a metric.
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve(workload: str, root: str | None = None) -> dict:
    """The cell ``workload`` of ``<root>/BENCHMARK.json`` with its files read.
    ``root`` defaults to this checkout; the benchmark's files are looked up
    under ``<root>/<paths[0]>``."""
    root = root or os.path.dirname(HERE)
    bench = _json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"benchmark: no workload {workload!r}; "
                         f"BENCHMARK.json has {sorted(cells)}")
    cell = cells[workload]
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    base = os.path.join(root, bench["paths"][0])
    cell_file = os.path.join(base, "cells", workload + ".json")

    def applies(metric: dict) -> bool:
        return workload in metric.get("workloads", [workload])

    return {
        "name": workload, "chips": cell["chips"], "base": base,
        "config_name": config["name"],
        "config": _json(os.path.join(root, config["file"])),
        "traffic_name": cell["traffic"],
        "mix": _json(os.path.join(base, "traffic", cell["traffic"] + ".json")),
        "cell": _json(cell_file) if os.path.exists(cell_file) else {},
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
        "peaks": _json(os.path.join(base, "peaks.json")),
    }


def model(spec: dict):
    """``(program's family module, its config object, reference module)``.

    The family is ``deepspeed_tpu.models.<family>``, through the interface
    the families share (``init_params``, ``build``, the config dataclass)."""
    conf = spec["config"]
    family = importlib.import_module("deepspeed_tpu.models." + conf["family"])
    cfg = getattr(family, conf["config_class"])(
        **{field: conf[key] for field, key in conf["fields"].items()})
    reference = load_module(
        os.path.join(spec["base"], "reference", conf["family"] + ".py"),
        "benchmark_reference_" + conf["family"])
    return family, cfg, reference


def peaks_for(spec: dict, device_kind: str) -> dict:
    table = spec["peaks"]["devices"]
    if device_kind not in table:
        raise SystemExit(f"benchmark: no published peaks for device kind "
                         f"{device_kind!r} in peaks.json ({sorted(table)})")
    return table[device_kind]


def layer_readers(spec: dict) -> dict:
    """``{metric name: (entry of BENCHMARK.json, read function)}``."""
    out = {}
    for m in spec["per_layer"]:
        mod = load_module(os.path.join(spec["base"], "layer_metrics",
                                       m["name"] + ".py"),
                          "benchmark_metric_" + m["name"].replace(".", "_").replace("-", "_"))
        out[m["name"]] = (m, mod.read)
    return out


def kernels(spec: dict) -> dict:
    """Every ``kernels/*.json``, by file name."""
    d = os.path.join(spec["base"], "kernels")
    return {f[:-5]: _json(os.path.join(d, f))
            for f in sorted(os.listdir(d)) if f.endswith(".json")}
