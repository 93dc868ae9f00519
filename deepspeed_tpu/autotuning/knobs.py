"""KnobSpace: the registry of tunable performance knobs (docs/AUTOTUNING.md).

The framework grew ~20 interacting perf knobs across two engines
(prefill_tile x kv budgets x quant codec x grad_overlap bucket/sharding x
pipeline shape x headroom guard). The search driver (autotuner.KnobSearch)
needs three facts per knob that the config dataclasses don't carry:

- its **domain** — the candidate values worth measuring;
- the **subsystem it patches** — a dotted train-config path or a
  ``RaggedConfig`` field, which is also how a persisted profile is applied
  back at startup (profiles.py);
- a **cost-model hint** — extra device bytes a value costs relative to the
  knob's default, so the headroom pruner can reject a candidate *before*
  paying a compile. Train-side memory is modeled by ``ModelInfo``
  (state_bytes/activation_bytes) instead of per-knob hints because the
  stage x micro-batch x remat x sharded-update interaction is one formula,
  not a sum of independent costs.

The registry is versioned: its signature is folded into the profile content
key, so a knob-space change invalidates persisted profiles instead of
silently replaying overrides whose meaning moved.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable

KNOBSPACE_VERSION = 1

TRAIN = "train"
SERVE = "serve"


@dataclass(frozen=True)
class Knob:
    """One tunable: domain + patch target + cost hint.

    ``name`` doubles as the patch address: a dotted ``Config`` path for
    train knobs (``zero_optimization.grad_overlap.bucket_bytes``), a
    ``RaggedConfig`` field name for serve knobs (``prefill_tile``).
    """

    name: str
    subsystem: str  # TRAIN | SERVE
    domain: tuple
    default: object
    # continuous knobs get a neighborhood-refinement pass around the winner
    continuous: bool = False
    # (value, ctx) -> extra device bytes vs the default; ctx carries
    # model/workload facts the caller knows (kv_pool_bytes, n_dev, ...)
    cost_hint: Callable | None = None
    doc: str = ""

    def cost_bytes(self, value, ctx: dict | None = None) -> float:
        if self.cost_hint is None:
            return 0.0
        try:
            return float(self.cost_hint(value, ctx or {}))
        except Exception:
            return 0.0

    def neighbors(self, value) -> list:
        """Refinement candidates around ``value`` (continuous knobs only):
        halve/double for numeric knobs, clamped to the domain hull so the
        neighborhood never wanders past what the registry declared sane."""
        if not self.continuous or isinstance(value, bool):
            return []
        if isinstance(value, int):
            lo, hi = min(self.domain), max(self.domain)
            return [v for v in (value // 2, value * 2)
                    if lo <= v <= hi and v != value and v > 0]
        if isinstance(value, float):
            lo, hi = min(self.domain), max(self.domain)
            return [round(v, 6) for v in (value / 2, value * 2)
                    if lo <= v <= hi and abs(v - value) > 1e-9]
        return []


class KnobSpace:
    """Ordered knob registry; the order is the coordinate-ascent sweep
    order (upstream knobs first: the micro-batch/stage shape decides what
    the overlap/dispatch knobs even mean)."""

    def __init__(self, version: int = KNOBSPACE_VERSION):
        self.version = version
        self._knobs: dict[str, Knob] = {}

    def register(self, knob: Knob) -> Knob:
        if knob.subsystem not in (TRAIN, SERVE):
            raise ValueError(f"unknown subsystem {knob.subsystem!r}")
        if knob.name in self._knobs:
            raise ValueError(f"knob {knob.name!r} already registered")
        if knob.default not in knob.domain:
            raise ValueError(
                f"knob {knob.name!r}: default {knob.default!r} not in domain")
        self._knobs[knob.name] = knob
        return knob

    def get(self, name: str) -> Knob:
        return self._knobs[name]

    def knobs(self, subsystem: str | None = None,
              names=None) -> list[Knob]:
        out = [k for k in self._knobs.values()
               if subsystem is None or k.subsystem == subsystem]
        if names is not None:
            wanted = list(names)
            missing = [n for n in wanted if n not in self._knobs]
            if missing:
                raise KeyError(f"unknown knobs {missing}")
            out = [k for k in out if k.name in wanted]
            out.sort(key=lambda k: wanted.index(k.name))
        return out

    def defaults(self, subsystem: str) -> dict:
        return {k.name: k.default for k in self.knobs(subsystem)}

    def signature(self) -> str:
        """Stable identity folded into profile content keys: version +
        every (name, domain) pair. Changing a domain or adding a knob
        changes the signature -> old profiles go stale by construction."""
        parts = [f"v{self.version}"]
        for name in sorted(self._knobs):
            k = self._knobs[name]
            parts.append(f"{name}:{k.subsystem}:{tuple(k.domain)!r}")
        return "|".join(parts)


def _kv_pool_scale(multiplier: float):
    """Cost hint for knobs that scale the KV pool's resident bytes."""
    def hint(value, ctx):
        return (multiplier - 1.0) * float(ctx.get("kv_pool_bytes", 0))
    return hint


def _build_default_space() -> KnobSpace:
    s = KnobSpace()
    # ---- train (dotted Config paths; memory interaction modeled by
    # ModelInfo in the driver, so no per-knob cost hints here) ----
    s.register(Knob("zero_optimization.stage", TRAIN, (0, 1, 2, 3), 0,
                    doc="ZeRO partition stage"))
    s.register(Knob("train_micro_batch_size_per_device", TRAIN,
                    (1, 2, 4, 8, 16), 2, continuous=True,
                    doc="per-device micro batch"))
    s.register(Knob("activation_checkpointing.enabled", TRAIN,
                    (False, True), False, doc="remat activations"))
    s.register(Knob("zero_optimization.grad_overlap.enabled", TRAIN,
                    (False, True), False,
                    doc="bucketed async grad collectives"))
    s.register(Knob("zero_optimization.grad_overlap.bucket_bytes", TRAIN,
                    (1 << 20, 4 << 20, 16 << 20), 4 << 20, continuous=True,
                    doc="overlap bucket size"))
    s.register(Knob("zero_optimization.grad_overlap.sharded_update", TRAIN,
                    (True, False), True,
                    doc="ZeRO-1 sharded optimizer update on the overlap path"))
    # ---- serve (RaggedConfig field names) ----
    s.register(Knob("prefill_tile", SERVE, (0, 16, 64), 0,
                    doc="tiled prefill kernel tile"))
    s.register(Knob("enable_prefix_cache", SERVE, (False, True), False,
                    doc="block-level prefix cache"))
    s.register(Knob("quant", SERVE, ("off", "int8", "fp8"), "off",
                    # int8/fp8 KV halves the pool's resident bytes
                    cost_hint=_kv_pool_scale(0.5),
                    doc="KV-block quantization codec"))
    s.register(Knob("kv_tier_host_blocks", SERVE, (64, 128, 256), 64,
                    continuous=True,
                    doc="host-RAM KV tier budget (off-device: free on HBM)"))
    s.register(Knob("headroom_guard_fraction", SERVE,
                    (0.02, 0.05, 0.1), 0.05, continuous=True,
                    doc="bytes_limit fraction held back from admission"))
    return s


DEFAULT_SPACE = _build_default_space()
