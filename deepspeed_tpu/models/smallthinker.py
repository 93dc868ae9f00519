"""SmallThinker-family sparse-MoE causal LM (PowerInfer SmallThinker-21BA3B).

A Mixtral-like backbone (RMSNorm, GQA, an expert FFN in every layer) with
three departures, each per layer and read off the config's own lists:

- **Two kinds of attention layer.** ``sliding_window_layout[l] == 1``: the
  layer attends over the last ``sliding_window`` keys only (``i - W < j <=
  i``, the query's own included); ``0``: over every key. ``rope_layout[l] ==
  1``: q and k are rotated (all lanes, half-split pairs); ``0``: they stay as
  projected (NoPE). Published: ``[0, 1, 1, 1] x 13`` for both, a full NoPE
  layer and three windowed RoPE layers, thirteen times.
- **The router reads the layer's input**: ``r = x W_r`` on the un-normed
  residual stream, before the input norm and before attention; the experts
  compute on the post-attention norm (``routed_experts(router_h=x)``).
- **ReLU-gated experts**: ``w_down(relu(h w_gate) * (h w_up))``
  (``routed_experts(gate_act="relu")``), top-6 of 64 by logit, weights the
  softmax over the picked logits; no shared expert, no scale, no bias.

On the serving path the two kinds live in two pools (``models/paged.py``,
*Sliding leaves*): the full layers' K and V in ``cache["k"]`` / ``["v"]``
``[L_f, NB, BS, Hkv*D]`` behind the block table every family has, the window
layers' in ``cache["swa"]`` ``[L_w, NB_w, BS, Hkv*D]`` behind a second table,
whose blocks the engine takes back as a sequence's window slides past them
(``ModelSpec.sliding_window``). The scan's body is one period of the layer
pattern (``_scan_periods``): four layer bodies a step program at the
published order. (One body a kind, the three window layers an inner scan,
was tried: whether the run's weights are the inner scan's operand or indexed
out of their stack, the compiler copies them, 0.3-5 GB a step at the
published widths, AOT for a v5e, PERF.md section 6, PR 44.)

Weights lie by scan position: ``params["lead"]`` (a list of layers before
the period), ``params["period"]`` (one tree a position of the period, leaves
``[repeats, ...]``). One rank's share of an expert-parallel
deployment: ``experts_held`` of the ``num_experts`` routed experts
(``expert_rank`` says which), the router over all of them.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

from deepspeed_tpu.models.api import ModelSpec, ShardCtx, causal_lm_loss
from deepspeed_tpu.models.experts import (
    expert_form,
    expert_stacks,
    routed_experts,
    routed_experts_einsum,
)
from deepspeed_tpu.models.llama import rmsnorm
from deepspeed_tpu.ops.attention import apply_rope, xla_attention

# the step programs' decode-row ladder starts at the slots of the benchmark's
# engine: a padding decode row costs one kernel step a layer, and every
# bucket is a step program beside each tile count to compile cold
DECODE_BUCKET_MIN = 16

# The seeded weights (``init_params``): every matrix N(0, gain / sqrt(rows it
# sums over)), so a unit row in gives ``gain`` out at any width, the
# embedding's rows unit. The gains are chosen so that 52 random layers keep
# what a comparison of logits needs: rows that differ, and attention that
# reads single keys. With every matrix N(0, 0.02) and the output projections
# N(0, 0.02 / sqrt(2 L)) (the other families' draw) a layer's attention
# output, where the keys agree, is six times the embedding it is added to,
# and what the rows share grows by that factor a layer: every row of a
# sequence ends on the same vector, greedy decoding repeats ONE token from
# its first step, and neither a window's edge off by a block nor any other
# small fault moves a logit (PERF.md section 6, PR 44, read on the chip).
# ``QK_SCORE_STD``: a query's scores over random keys are this wide (at 1.0
# ~1,500 of a window's 4,096 keys share a row's weight; at 3.0 one to three
# hold it, as in a trained model's sharp heads, and a key that falls off the
# window's edge takes a head's output with it). ``ATTN_OUT_GAIN``: a layer's
# attention adds this much of a unit row where its keys agree, so what the
# rows share grows slowly and the 52 layers' attention ends at about the
# embedding's norm. ``EXPERT_OUT_GAIN``: an expert's output likewise (0.71 of
# it: the gate is a ReLU), the held experts' sum over the depth about a third
# of the embedding's norm. Harder scores (4.0) or a larger attention gain
# (0.47) amplify bfloat16 rounding faster than the edge; softer scores (2.0)
# or the larger gain let the rows collapse again; larger experts put the
# router's flipped picks over both
QK_SCORE_STD = 3.0
ATTN_OUT_GAIN = 0.35
EXPERT_OUT_GAIN = 0.163


@dataclass(frozen=True)
class SmallThinkerConfig:
    vocab_size: int = 151936
    hidden_size: int = 2560
    moe_intermediate_size: int = 768
    num_layers: int = 52
    num_heads: int = 28
    num_kv_heads: int = 4
    head_dim: int = 128
    num_experts: int = 64          # the routed experts the router scores
    top_k: int = 6
    # one rank's share: experts ``expert_rank * experts_held ..`` of them
    # (None: every routed expert lives here)
    experts_held: int | None = None
    expert_rank: int = 0
    sliding_window: int = 4096
    # per layer, 1 = windowed / rotated (None: ``[0, 1, 1, 1]`` repeated)
    sliding_window_layout: tuple | None = None
    rope_layout: tuple | None = None
    rope_theta: float = 1500000.0
    rms_norm_eps: float = 1e-6
    max_seq_len: int = 16384

    def __post_init__(self):
        for name in ("sliding_window_layout", "rope_layout"):
            given = getattr(self, name)
            layout = tuple(int(v) for v in given) if given is not None else \
                tuple(int(i % 4 != 0) for i in range(self.num_layers))
            if len(layout) != self.num_layers:
                raise ValueError(f"{name} has {len(layout)} entries, the "
                                 f"model {self.num_layers} layers")
            object.__setattr__(self, name, layout)
        if self.held < 1 or self.num_experts % self.held \
                or not 0 <= self.expert_rank < self.num_experts // self.held:
            raise ValueError(
                f"experts_held={self.experts_held} / expert_rank="
                f"{self.expert_rank} is no rank's share of {self.num_experts}")

    @property
    def held(self) -> int:
        return self.num_experts if self.experts_held is None \
            else self.experts_held

    @property
    def held_range(self):
        """``routed_experts``' ``held``: (first held expert, routed experts);
        None where every routed expert lives here."""
        if self.held == self.num_experts:
            return None
        return (self.expert_rank * self.held, self.num_experts)

    @property
    def layer_pattern(self) -> str:
        """A letter a layer: ``F`` full NoPE, ``W`` window RoPE (the two the
        published model has), ``f`` full RoPE, ``w`` window NoPE."""
        return "".join(("fW" if r else "Fw")[w] for w, r in
                       zip(self.sliding_window_layout, self.rope_layout))

    def layers_of(self, windowed: bool) -> int:
        return sum(1 for w in self.sliding_window_layout if bool(w) == windowed)

    @staticmethod
    def tiny(vocab_size: int = 256) -> "SmallThinkerConfig":
        """Eight layers ``F W W W`` twice, a window of 12 tokens: over blocks
        of 4 it slides many times inside a 64-token sequence."""
        return SmallThinkerConfig(
            vocab_size=vocab_size, hidden_size=64, moe_intermediate_size=32,
            num_layers=8, num_heads=4, num_kv_heads=2, head_dim=16,
            num_experts=8, top_k=2, sliding_window=12, max_seq_len=128)


def _plan(cfg: SmallThinkerConfig):
    """``(lead, period, repeats)``: the layers before the period, the
    period, and how often it repeats (``models/paged.stack_plan``)."""
    from deepspeed_tpu.models.paged import stack_plan

    return stack_plan(cfg.layer_pattern)


def _layer_shapes(cfg: SmallThinkerConfig) -> dict:
    d, f, hd = cfg.hidden_size, cfg.moe_intermediate_size, cfg.head_dim
    hq, hkv, e = cfg.num_heads, cfg.num_kv_heads, cfg.held
    qk = QK_SCORE_STD ** 0.5
    return {
        "attn_norm": ((d,), None),
        "wq": ((d, hq * hd), qk),
        "wk": ((d, hkv * hd), qk),
        "wv": ((d, hkv * hd), 1.0),
        "wo": ((hq * hd, d), ATTN_OUT_GAIN),
        "mlp_norm": ((d,), None),
        "router": ((d, cfg.num_experts), 1.0),
        "w_gate": ((e, d, f), 1.0),
        "w_up": ((e, d, f), 1.0),
        "w_down": ((e, f, d), EXPERT_OUT_GAIN),
    }


def init_params(cfg: SmallThinkerConfig, rng) -> dict:
    """Seeded weights: a matrix N(0, gain / sqrt(its second-last size)) with
    the gains above (1.0 but for wq, wk, wo and w_down), the embedding's rows
    N(0, 1), norms at one; drawn by the device's own generator
    (``nemotron_h.init_params`` says why)."""
    lead, period, repeats = _plan(cfg)
    rng = jax.random.wrap_key_data(jax.random.bits(rng, (4,), jnp.uint32),
                                   impl="rbg")
    shapes = _layer_shapes(cfg)
    drawn = sum(gain is not None for _, gain in shapes.values())
    k = iter(jax.random.split(rng, 2 + drawn * (len(lead) + len(period))))

    def leaf(stack, shape, gain):
        if gain is None:
            return jnp.ones(stack + shape, jnp.float32)
        return jax.random.normal(next(k), stack + shape, jnp.float32) * (
            gain / shape[-2] ** 0.5)

    def layer(stack=()):
        return {name: leaf(stack, *s) for name, s in shapes.items()}

    d = cfg.hidden_size
    return {
        "embed": jax.random.normal(next(k), (cfg.vocab_size, d), jnp.float32),
        "lead": [layer() for _ in lead],
        "period": [layer((repeats,)) for _ in period],
        "final_norm": jnp.ones((d,), jnp.float32),
        "lm_head": leaf((), (d, cfg.vocab_size), 1.0),
    }


def param_logical_axes(cfg: SmallThinkerConfig) -> dict:
    lead, period, _ = _plan(cfg)
    layer = {
        "attn_norm": ("embed",), "wq": ("embed", "heads"),
        "wk": ("embed", "kv_heads"), "wv": ("embed", "kv_heads"),
        "wo": ("heads", "embed"), "mlp_norm": ("embed",),
        "router": ("embed", None), "w_gate": ("experts", "embed", "ffn"),
        "w_up": ("experts", "embed", "ffn"),
        "w_down": ("experts", "ffn", "embed"),
    }
    return {
        "embed": ("vocab", "embed"),
        "lead": [dict(layer) for _ in lead],
        "period": [{k: ("layers",) + v for k, v in layer.items()}
                   for _ in period],
        "final_norm": ("embed",),
        "lm_head": ("embed", "vocab"),
    }


def layers_in_order(cfg: SmallThinkerConfig, params):
    """``[(kind letter, layer tree)]`` in published order (host-side
    indexing of the stacks: tests and ``forward``)."""
    lead, period, repeats = _plan(cfg)
    out = list(zip(lead, params["lead"]))
    for r in range(repeats):
        out += [(kind, jax.tree_util.tree_map(lambda a: a[r], tree))
                for kind, tree in zip(period, params["period"])]
    return out


def _qkv(cfg: SmallThinkerConfig, kind: str, h, lp, positions):
    """The layer's projections of the normed rows ``h`` [T, D]: ``q`` [T,
    Hq, hd], ``k`` / ``v`` [T, Hkv, hd], rotated where the layer's
    ``rope_layout`` says so (``kind`` ``W`` / ``f``). Through the paged
    contract's ``rows_to_heads``; the plain ``forward`` shares it and so its
    pin, which changes no value there either."""
    from deepspeed_tpu.models.paged import rows_to_heads

    q = rows_to_heads(h, lp["wq"], cfg.num_heads)
    k = rows_to_heads(h, lp["wk"], cfg.num_kv_heads)
    v = rows_to_heads(h, lp["wv"], cfg.num_kv_heads)
    if kind in "Wf":
        q, k = apply_rope(q[None], k[None], positions[None], cfg.rope_theta)
        q, k = q[0], k[0]
    return q, k, v


def _window(cfg: SmallThinkerConfig, kind: str) -> int | None:
    return cfg.sliding_window if kind in "Ww" else None


def forward(cfg: SmallThinkerConfig, params, input_ids,
            ctx: ShardCtx | None = None):
    """``[B, S]`` token ids -> ``[B, S, V]`` logits, dropless, plain XLA
    (the all-experts einsum, a masked softmax): the training-shaped pass the
    spec carries; the serving path is ``ragged_forward``."""
    del ctx
    b, s = input_ids.shape
    positions = jnp.arange(s)
    x = params["embed"][input_ids]
    i, j = positions[:, None], positions[None, :]
    for kind, lp in layers_in_order(cfg, params):
        lp = jax.tree_util.tree_map(lambda a: a.astype(x.dtype), lp)
        h = rmsnorm(x, lp["attn_norm"], cfg.rms_norm_eps)
        q, k, v = jax.vmap(lambda hb: _qkv(cfg, kind, hb, lp, positions))(h)
        seen = j <= i
        if _window(cfg, kind) is not None:
            seen = seen & (i - j < cfg.sliding_window)
        o = xla_attention(q, k, v, causal=False,
                          bias=jnp.where(seen, 0.0, -1e30)[None, None])
        x1 = x + o.reshape(b, s, -1) @ lp["wo"]
        h2 = rmsnorm(x1, lp["mlp_norm"], cfg.rms_norm_eps)
        y = routed_experts_einsum(
            h2.reshape(b * s, -1), lp["router"], lp["w_gate"], lp["w_up"],
            lp["w_down"], cfg.top_k, held=cfg.held_range,
            router_h=x.reshape(b * s, -1), gate_act="relu")
        x = x1 + y.reshape(b, s, -1)
    x = rmsnorm(x, params["final_norm"].astype(x.dtype), cfg.rms_norm_eps)
    return x @ params["lm_head"].astype(x.dtype)


# ------------------------------------------------------------------ inference
def init_paged_cache(cfg: SmallThinkerConfig, num_blocks: int, block_size: int,
                     dtype=jnp.bfloat16, codec=None, num_slots=None) -> dict:
    """The two pools of the paged contract's *Sliding leaves*
    (``models/paged.py``): the full layers' ``{"k", "v"}`` ``[L_f,
    num_blocks, BS, Hkv*D]`` and under ``"swa"`` the window layers' ``[L_w,
    NB_w, BS, Hkv*D]`` with ``NB_w = max_seqs x (W / BS + 1) + 1``: what every
    slot can hold of a window, and the scratch block."""
    from deepspeed_tpu.models.paged import (SWA, init_paged_pool,
                                            sliding_blocks_per_seq)

    if codec is not None:
        raise NotImplementedError(
            "smallthinker: a quantized pool beside sliding leaves is not "
            "implemented (the engine refuses it too)")
    if num_slots is None:
        raise ValueError("smallthinker: the cache needs the engine's slot "
                         "count (num_slots = max_seqs + 1) to size its "
                         "sliding pool")
    n_w = cfg.layers_of(True)
    pools = init_paged_pool(cfg.num_layers - n_w, num_blocks, block_size,
                            cfg.num_kv_heads, cfg.head_dim, dtype)
    if n_w:
        per_seq = min(sliding_blocks_per_seq(cfg.sliding_window, block_size),
                      -(-cfg.max_seq_len // block_size))
        pools[SWA] = init_paged_pool(
            n_w, (num_slots - 1) * per_seq + 1, block_size, cfg.num_kv_heads,
            cfg.head_dim, dtype)
    return pools


def _ragged_layer(cfg: SmallThinkerConfig, kind: str, x, lp, kc, vc, positions,
                  slots, tables, prefill_tiles, stacked):
    """One decoder layer over a flat ragged token batch [T, D]; ``kc`` /
    ``vc`` the pool of the layer's kind, ``tables`` its table."""
    from deepspeed_tpu.models.paged import ragged_pool_attention, write_kv_paged
    from deepspeed_tpu.ops.quantizer import dequantize_layer

    lp = dequantize_layer(lp, x.dtype)
    t_tokens = x.shape[0]
    h = rmsnorm(x, lp["attn_norm"], cfg.rms_norm_eps)
    q, kk, vv = _qkv(cfg, kind, h, lp, positions)
    kc, vc = write_kv_paged(kc, vc, kk, vv, slots, positions, tables,
                            prefill_tiles)
    o = ragged_pool_attention(q, kc, vc, slots, positions, tables,
                              prefill_tiles, window=_window(cfg, kind)
                              ).astype(x.dtype)
    x1 = x + o.reshape(t_tokens, -1) @ lp["wo"]
    h2 = rmsnorm(x1, lp["mlp_norm"], cfg.rms_norm_eps)
    y = routed_experts(
        h2, lp["router"], lp["w_gate"], lp["w_up"], lp["w_down"], cfg.top_k,
        stacked=stacked and (*stacked, lp["first_expert"]),
        held=cfg.held_range, router_h=x, gate_act="relu")
    return x1 + y, kc, vc


def ragged_forward(cfg: SmallThinkerConfig, params, tokens, slots, positions,
                   block_tables, cache, prefill_tiles=None):
    """Flat ragged step: [T] mixed tokens -> ([T, V] logits, cache).
    ``block_tables`` is ``(full table, sliding table)`` where the cache has
    sliding leaves; a layer addresses the pool of its kind through the table
    of its kind (``models/paged._scan_periods``)."""
    from deepspeed_tpu.models.paged import SWA, scan_layers_paged
    from deepspeed_tpu.ops.quantizer import maybe_dequantize

    lead, period, _ = _plan(cfg)

    def layer(kind, stacked):
        where = "swa" if _window(cfg, kind) is not None else "block"

        def fn(x, lp, pool, tables):
            kv = pool[SWA] if where == "swa" else pool
            x, kc, vc = _ragged_layer(cfg, kind, x, lp, kv["k"], kv["v"],
                                      positions, slots, tables, prefill_tiles,
                                      stacked)
            kv = {"k": kc, "v": vc}
            return x, ({**pool, SWA: kv} if where == "swa" else {**pool, **kv})

        return where, fn

    trees, stacks = zip(*(expert_stacks(tree) for tree in params["period"]))
    x = params["embed"][tokens].astype(cache["k"].dtype)
    x, cache = scan_layers_paged(
        [layer(kind, st) for kind, st in zip(period, stacks)], x, trees,
        cache, block_tables,
        lead=[(*layer(kind, None), lp)
              for kind, lp in zip(lead, params["lead"])])
    x = rmsnorm(x, params["final_norm"], cfg.rms_norm_eps)
    logits = x @ maybe_dequantize(params["lm_head"], x.dtype).astype(x.dtype)
    return logits, cache


# ------------------------------------------------------------- arithmetic
def _layer_params(cfg: SmallThinkerConfig, experts: float) -> float:
    d, f, hd = cfg.hidden_size, cfg.moe_intermediate_size, cfg.head_dim
    return (d * hd * (2 * cfg.num_heads + 2 * cfg.num_kv_heads)
            + d * cfg.num_experts + 2 * d + experts * 3 * d * f)


def num_params(cfg: SmallThinkerConfig) -> int:
    """Parameters that live here: the held experts, not all the routed."""
    d = cfg.hidden_size
    return int(2 * cfg.vocab_size * d + d
               + cfg.num_layers * _layer_params(cfg, cfg.held))


def flops_per_token(cfg: SmallThinkerConfig, seq_len: int) -> float:
    """Active-parameter training FLOPs of this rank's share (``top_k x held
    / num_experts`` experts a token and layer) plus attention over
    ``seq_len``, the window layers over no more than their window."""
    active = cfg.vocab_size * cfg.hidden_size + cfg.num_layers * _layer_params(
        cfg, cfg.top_k * cfg.held / cfg.num_experts)
    keys = (cfg.layers_of(False) * seq_len
            + cfg.layers_of(True) * min(seq_len, 2 * cfg.sliding_window))
    return 6.0 * active + 12.0 * cfg.num_heads * cfg.head_dim * keys / 2.0


def build(cfg: SmallThinkerConfig, ctx: ShardCtx | None = None) -> ModelSpec:
    ctx = ctx or ShardCtx()
    fwd = partial(forward, cfg, ctx=ctx)

    def loss_fn(params, batch, rng=None):
        del rng  # dropless routing draws nothing
        return causal_lm_loss(fwd(params, batch["input_ids"]),
                              batch["input_ids"], batch.get("labels"))

    return ModelSpec(
        name="smallthinker",
        config=cfg,
        init_fn=partial(init_params, cfg),
        loss_fn=loss_fn,
        forward_fn=fwd,
        param_logical_axes=param_logical_axes(cfg),
        logical_dim_units={"heads": cfg.num_heads,
                           "kv_heads": cfg.num_kv_heads, "experts": cfg.held},
        num_params=num_params(cfg),
        flops_per_token=partial(flops_per_token, cfg),
        init_paged_cache_fn=partial(init_paged_cache, cfg),
        ragged_forward_fn=partial(ragged_forward, cfg),
        supports_prefill_tiles=True,
        moe_form=partial(expert_form, num_experts=cfg.num_experts,
                         top_k=cfg.top_k),
        decode_bucket_min=DECODE_BUCKET_MIN,
        sliding_window=cfg.sliding_window if cfg.layers_of(True) else None,
    )
