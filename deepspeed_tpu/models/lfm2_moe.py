"""LFM2-MoE causal LM as LiquidAI/LFM2-8B-A1B configures it (``model_type:
lfm2_moe``): every layer is an OPERATOR, a gated short convolution or (one in
four) a grouped-query attention layer as the published ``layer_types`` says,
and then an FFN, dense in the first ``num_dense_layers`` layers and routed
experts in the rest, each behind its own RMSNorm (a weight, eps ``norm_eps``)
and its own plain residual add. ``x`` the residual stream, ``d`` its width.

- embedding: ``x = E[id]``, ``E`` ``[vocab, d]``.
- layer ``i``: ``x <- x + Op_i(RMSNorm(x; operator_norm))``, then ``x <- x +
  FFN_i(RMSNorm(x; ffn_norm))``.
- **``Op`` = gated short convolution** (``layer_types[i] == "conv"``,
  ``models/shortconv.py``): ``[B, C, u] = split_3(h W_in)``, ``W_in`` ``[d, 3
  d]``, no bias, in that order; ``z = B * u``; ``c_t = sum_{k=0..K-1} w_k *
  z_{t-K+1+k}`` with ``w`` ``[K, d]`` a depthwise causal filter (``K`` =
  ``conv_L_cache`` = 3), ``z`` zero before the sequence's first token, no bias
  (``conv_bias`` false) and NO activation; ``Op = (C * c) W_out``, ``W_out``
  ``[d, d]``. Carried from token to token: ``z_{t-1}, z_{t-2}`` and nothing
  else.
- **``Op`` = attention** (``"full_attention"``): ``q = h W_q`` as
  ``num_heads`` heads of ``head_dim`` (32 of 64), ``k = h W_k``, ``v = h W_v``
  as ``num_kv_heads`` heads (8), no bias; RMSNorm over each head's lanes on q
  (``q_norm``) and on k (``k_norm``), then RoPE (``rope_theta``, all the
  lanes, halves rotated), causal ``softmax(q k^T / sqrt(head_dim)) v``,
  ``num_heads / num_kv_heads`` query heads a K/V head, ``W_o`` ``[d, d]``.
- **``FFN``, ``i < num_dense_layers``**: ``W_2(silu(h W_1) * h W_3)``, width
  ``intermediate_size``.
- **``FFN``, the rest**: ``s = sigmoid(h W_r)`` in float32, ``W_r`` ``[d,
  num_experts]``; the ``top_k`` experts with the largest ``s + b`` are picked
  (``b`` ``[num_experts]``, the selection bias, ``use_expert_bias``: it picks
  and never weighs); ``w_e = s_e / (sum_picked s + 1e-6)``
  (``norm_topk_prob``), times ``routed_scaling_factor``; ``FFN = sum_picked
  w_e W_2^e(silu(h W_1^e) * h W_3^e)``, width ``moe_intermediate_size``. No
  shared expert. ``models/experts.routed_experts`` with ``scoring="sigmoid"``.
- logits: ``RMSNorm(x; final_norm) E^T``: the head is the embedding (the
  family ties them), read where it lies (a product over ``E``'s minor axis).

**The stack.** A layer's kind is its operator AND its FFN (``conv`` /
``full_attention`` x ``dense`` / ``moe``); the kinds are cut into RUNS of one
kind and the parameters are kept a run a stack (``params["runs"]``), so that a
step program scans each run where it lies (``paged.scan_runs_paged``, as
``granite_hybrid`` and ``jamba``). The published 24 layers are thirteen runs;
layers 0-11 (``c c a c c c a c c c a c``, the benchmark's stage) are seven:
2 ``conv/dense``, then ``a``, 3 ``c``, ``a``, 3 ``c``, ``a``, 1 ``c`` with
experts. ``paged.stack_plan_tail`` would make a lead of two, a period ``a c c
c`` scanned twice and a tail of two, eight bodies for those twelve layers.

**Serving.** The attention layers' K and V lie in the paged pool (``{"k",
"v"}`` of ``[L_attention, NB, BS, Hkv x head_dim]``: 512 lanes a row at the
published widths), the convolution layers' two carried rows beside it in a
slot leaf that is a window leaf alone (``shortconv.init_slot_leaves``:
``[L_conv, S, 32, 128]`` bfloat16, 8 KB a slot and layer). The attention path
is ``mixtral._ragged_layer``'s with the head norm (``sdar``), at 64 lanes a
head: ``rows_to_heads``, the norm, the rotation, ``write_kv_paged``,
``ragged_pool_attention``.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from itertools import groupby

import jax
import jax.numpy as jnp

from deepspeed_tpu.models import shortconv
from deepspeed_tpu.models.api import ModelSpec, ShardCtx, causal_lm_loss
from deepspeed_tpu.models.experts import (
    expert_form,
    expert_stacks,
    routed_experts,
    routed_experts_einsum,
    swiglu,
)
from deepspeed_tpu.models.llama import rmsnorm
from deepspeed_tpu.ops.attention import apply_rope, xla_attention

OPERATORS = ("conv", "full_attention")
PUBLISHED = tuple("full_attention" if i in (2, 6, 10, 14, 18, 21) else "conv"
                  for i in range(24))
# the seeded draw: what the projections back to the residual stream (``W_out``,
# ``W_o``, ``W_2``) are multiplied by, in every layer alike (``init_params``;
# ``Lfm2MoeConfig.stream_gain``'s default)
STREAM_GAIN = 4.0
# the eps under the router's normalisation over its picks (the family's
# published modelling code)
ROUTER_EPS = 1e-6
# ONE decode bucket up to 512 slots: a bucket is four step programs of seven
# layer bodies each, and a padding row costs this model next to nothing (its
# slot state is 72 KB, its K/V read is the scratch block's): PERF.md section
# 6, PR 62, has both ladders measured
DECODE_BUCKET_MIN = 512


@dataclass(frozen=True)
class Lfm2MoeConfig:
    vocab_size: int = 65536
    hidden_size: int = 2048
    num_layers: int = 24
    layer_types: tuple = PUBLISHED
    conv_kernel: int = 3                  # ``conv_L_cache``
    conv_bias: bool = False
    num_heads: int = 32
    num_kv_heads: int = 8
    rope_theta: float = 1e6
    num_dense_layers: int = 2
    intermediate_size: int = 7168
    num_experts: int = 32
    moe_intermediate_size: int = 1792
    top_k: int = 4
    norm_topk_prob: bool = True
    use_expert_bias: bool = True
    routed_scaling_factor: float = 1.0
    rms_norm_eps: float = 1e-5
    # the seeded draw of the projections back to the stream (``init_params``)
    stream_gain: float = STREAM_GAIN
    max_seq_len: int = 128000

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if len(self.layer_types) != self.num_layers \
                or set(self.layer_types) - set(OPERATORS):
            raise ValueError(
                "lfm2_moe: layer_types must name each of the "
                f"{self.num_layers} layers as one of {OPERATORS}")
        if self.hidden_size % self.num_heads \
                or self.num_heads % self.num_kv_heads:
            raise ValueError("lfm2_moe: num_heads must divide hidden_size and "
                             "num_kv_heads num_heads")
        if self.conv_bias:
            raise NotImplementedError(
                "lfm2_moe: a bias on the short convolution is not built; "
                "LFM2-8B-A1B's has none (conv_bias false)")
        if not 0 <= self.num_dense_layers <= self.num_layers:
            raise ValueError("lfm2_moe: num_dense_layers counts leading layers")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @property
    def kinds(self) -> tuple:
        """Each layer's ``(operator, ffn)``: ``ffn`` is ``"dense"`` in the
        first ``num_dense_layers`` layers and ``"moe"`` after."""
        return tuple((op, "dense" if i < self.num_dense_layers else "moe")
                     for i, op in enumerate(self.layer_types))

    @property
    def runs(self) -> list:
        """``[((operator, ffn), layers)]``: ``kinds`` as runs of one kind."""
        return [(kind, len(list(g))) for kind, g in groupby(self.kinds)]

    def layers_of(self, operator: str) -> int:
        return self.layer_types.count(operator)

    @staticmethod
    def tiny(vocab_size: int = 256, layer_types=(
            "conv", "conv", "full_attention", "conv", "conv",
            "full_attention", "conv"), **over) -> "Lfm2MoeConfig":
        """Seven layers, runs of 2 (dense), 1, 2, 1, 1: every kind of run a
        step program has, two of them scanned. At 64 lanes a layer writes far
        less beside the embedding than at the published width (the gated
        convolution's output is cubic in its projections' 0.02 sqrt(d)), so
        the gain is larger here."""
        return Lfm2MoeConfig(**{**dict(
            vocab_size=vocab_size, hidden_size=64, num_layers=len(layer_types),
            layer_types=layer_types, num_heads=4, num_kv_heads=2,
            num_dense_layers=2, intermediate_size=96, num_experts=8,
            moe_intermediate_size=32, top_k=2, stream_gain=64.0,
            max_seq_len=128), **over})


def init_params(cfg: Lfm2MoeConfig, rng) -> dict:
    """Seeded weights, a run of layers a stack (module doc). std 0.02; the
    filter uniform in +-1/sqrt(K) (``shortconv.init_mixer``); the selection
    bias N(0, 0.01), small and non-zero, so that selection (with it) and
    weighting (without it) differ, as ``deepseek`` draws it. The device's own
    generator (``rbg``), as ``granite_hybrid``.

    The projections back to the residual stream (``W_out``, ``W_o``, ``W_2``)
    are ``cfg.stream_gain`` x 0.02 / sqrt(2 x layers), in every layer alike.
    Why a gain: the head is the embedding and the stream starts as
    ``E[token]``, so whatever of it is left under the layers' outputs reads
    back as the input token's OWN logit, and at gain 1 these layers write too
    little beside it (the experts' four weights sum to one; ROADMAP B9): a
    served token would repeat its input whatever the layers compute. Why not
    ``granite_hybrid``'s geometric growth over depth: it leaves the logits to
    the last layers alone, whose bfloat16 error (and a flipped pick of the
    last router) then shows undamped, and no check sees the first layers;
    with one gain every layer weighs alike (PERF.md section 6, PR 62, has
    both forms' readings)."""
    d = cfg.hidden_size
    rng = jax.random.wrap_key_data(jax.random.bits(rng, (4,), jnp.uint32),
                                   impl="rbg")
    k_embed, *run_keys = jax.random.split(rng, 1 + len(cfg.runs))
    std = 0.02
    out_std = cfg.stream_gain * std / (2.0 * cfg.num_layers) ** 0.5

    def norm(key, *shape, s=std):
        return jax.random.normal(key, shape, jnp.float32) * s

    def run(kind: tuple, n: int, key) -> dict:
        op, ffn = kind
        k = iter(jax.random.split(key, 16))
        if op == "conv":
            mix = shortconv.init_mixer(cfg, n, k, std, out_std)
        else:
            hq, hkv = (h * cfg.head_dim for h in (cfg.num_heads, cfg.num_kv_heads))
            mix = {"wq": norm(next(k), n, d, hq), "wk": norm(next(k), n, d, hkv),
                   "wv": norm(next(k), n, d, hkv),
                   "q_norm": jnp.ones((n, cfg.head_dim), jnp.float32),
                   "k_norm": jnp.ones((n, cfg.head_dim), jnp.float32),
                   "wo": norm(next(k), n, hq, d, s=out_std)}
        if ffn == "dense":
            f = cfg.intermediate_size
            ffn_w = {"w_gate": norm(next(k), n, d, f),
                     "w_up": norm(next(k), n, d, f),
                     "w_down": norm(next(k), n, f, d, s=out_std)}
        else:
            e, f = cfg.num_experts, cfg.moe_intermediate_size
            ffn_w = {"router": norm(next(k), n, d, e),
                     "router_bias": norm(next(k), n, e, s=0.01),
                     "w_gate": norm(next(k), n, e, d, f),
                     "w_up": norm(next(k), n, e, d, f),
                     "w_down": norm(next(k), n, e, f, d, s=out_std)}
        return {"norm": jnp.ones((n, d), jnp.float32), "mix": mix,
                "ffn_norm": jnp.ones((n, d), jnp.float32), "ffn": ffn_w}

    return {
        "embed": norm(k_embed, cfg.vocab_size, d),
        "runs": [run(kind, n, key)
                 for (kind, n), key in zip(cfg.runs, run_keys)],
        "final_norm": jnp.ones((d,), jnp.float32),
    }


_ATTN_AXES = {
    "wq": ("layers", "embed", "heads"),
    "wk": ("layers", "embed", "kv_heads"),
    "wv": ("layers", "embed", "kv_heads"),
    "q_norm": ("layers", None),
    "k_norm": ("layers", None),
    "wo": ("layers", "heads", "embed"),
}
_DENSE_AXES = {
    "w_gate": ("layers", "embed", "ffn"),
    "w_up": ("layers", "embed", "ffn"),
    "w_down": ("layers", "ffn", "embed"),
}
_MOE_AXES = {
    "router": ("layers", "embed", None),
    "router_bias": ("layers", None),
    "w_gate": ("layers", "experts", "embed", "ffn"),
    "w_up": ("layers", "experts", "embed", "ffn"),
    "w_down": ("layers", "experts", "ffn", "embed"),
}


def param_logical_axes(cfg: Lfm2MoeConfig) -> dict:
    return {
        "embed": ("vocab", "embed"),
        "runs": [{"norm": ("layers", "embed"),
                  "mix": shortconv.LOGICAL_AXES if op == "conv" else _ATTN_AXES,
                  "ffn_norm": ("layers", "embed"),
                  "ffn": _DENSE_AXES if ffn == "dense" else _MOE_AXES}
                 for (op, ffn), _ in cfg.runs],
        "final_norm": ("embed",),
    }


# ------------------------------------------------------------------ layers
def _ffn_sublayer(cfg: Lfm2MoeConfig, x, lp, experts, **stacked):
    """``x + FFN(RMSNorm(x))``: the dense gated MLP where the layer's weights
    have no router, else the routed experts through ``experts``."""
    h = rmsnorm(x, lp["ffn_norm"], cfg.rms_norm_eps)
    ffn = lp["ffn"]
    if "router" not in ffn:
        y = swiglu(h, ffn["w_gate"], ffn["w_up"], ffn["w_down"])
    else:
        y = experts(
            h.reshape(-1, h.shape[-1]), ffn["router"], ffn["w_gate"],
            ffn["w_up"], ffn["w_down"], cfg.top_k, **stacked,
            scoring="sigmoid",
            bias=ffn["router_bias"] if cfg.use_expert_bias else None,
            renormalize=cfg.norm_topk_prob, scale=cfg.routed_scaling_factor,
            eps=ROUTER_EPS).reshape(h.shape)
    return x + y.astype(x.dtype)


def _head(cfg: Lfm2MoeConfig, params, x):
    """``RMSNorm(x) E^T`` over the table's minor axis."""
    x = rmsnorm(x, params["final_norm"], cfg.rms_norm_eps)
    return jnp.einsum("...d,vd->...v", x, params["embed"].astype(x.dtype))


def forward(cfg: Lfm2MoeConfig, params, input_ids,
            ctx: ShardCtx | None = None):
    """``[B, S]`` token ids -> ``[B, S, V]`` logits: the plain forward pass
    (no cache), the layers in ``layer_types``' order; the experts through the
    einsum form."""
    ctx = ctx or ShardCtx()
    b, s = input_ids.shape
    pos = jnp.broadcast_to(jnp.arange(s), (b, s))
    x = ctx.embed_lookup(params["embed"], input_ids, "batch", "seq", "embed_act")
    for ((op, _), n), stack in zip(cfg.runs, params["runs"]):
        for i in range(n):
            lp = ctx.layer_weights(
                jax.tree_util.tree_map(lambda a: a[i], stack), x.dtype)  # noqa: B023
            h = rmsnorm(x, lp["norm"], cfg.rms_norm_eps)
            mix = lp["mix"]
            if op == "conv":
                o = jax.vmap(partial(shortconv.sequence, cfg, mix))(h)
            else:
                q, k, v = ((h @ mix[w]).reshape(b, s, heads, cfg.head_dim)
                           for w, heads in (("wq", cfg.num_heads),
                                            ("wk", cfg.num_kv_heads),
                                            ("wv", cfg.num_kv_heads)))
                q = rmsnorm(q, mix["q_norm"], cfg.rms_norm_eps)
                k = rmsnorm(k, mix["k_norm"], cfg.rms_norm_eps)
                q, k = apply_rope(q, k, pos, cfg.rope_theta)
                o = xla_attention(q, k, v, causal=True)
                o = o.reshape(b, s, -1) @ mix["wo"]
            x = _ffn_sublayer(cfg, x + o, lp, routed_experts_einsum)
            x = ctx.constrain(x, "batch", "seq", "embed_act")
    return ctx.constrain(_head(cfg, params, x), "batch", "seq", "vocab_act")


# ------------------------------------------------------------------ inference
def init_paged_cache(cfg: Lfm2MoeConfig, num_blocks: int, block_size: int,
                     dtype=jnp.bfloat16, codec=None,
                     num_slots: int | None = None) -> dict:
    """The cache of the ragged engine (``models/paged.py``): the attention
    layers' pool as block leaves, ``{"k", "v"}`` of ``[L_attention,
    num_blocks, block_size, Hkv x D]``, and the convolution layers' carried
    rows as the one slot leaf under ``"slots"``
    (``shortconv.init_slot_leaves``)."""
    from deepspeed_tpu.models.paged import SLOTS, init_paged_pool

    if codec is not None:
        raise NotImplementedError(
            "lfm2_moe: a quantized pool is not implemented beside slot state "
            "(the engine refuses it too)")
    if num_slots is None:
        raise ValueError("lfm2_moe: the cache needs the engine's slot count "
                         "(num_slots = max_seqs + 1) for its convolution rows")
    cache = init_paged_pool(cfg.layers_of("full_attention"), num_blocks,
                            block_size, cfg.num_kv_heads, cfg.head_dim, dtype)
    cache[SLOTS] = shortconv.init_slot_leaves(cfg, cfg.layers_of("conv"),
                                              num_slots, dtype)
    return cache


def attention_ragged(cfg: Lfm2MoeConfig, h, lp, pool, layer_tables, slots,
                     positions, prefill_tiles):
    """The attention operator over the normed rows ``h`` [T, D] of a flat
    ragged token batch: rows to heads, the head norm on q and k, the
    rotation, this layer's rows written to ``pool["k"]`` / ``pool["v"]``
    through its table and read back by the two paged kernels, then ``W_o``.
    Returns ``(out [T, D], pool)``."""
    from deepspeed_tpu.models.paged import (
        ragged_pool_attention,
        rows_to_heads,
        write_kv_paged,
    )

    q = rmsnorm(rows_to_heads(h, lp["wq"], cfg.num_heads), lp["q_norm"],
                cfg.rms_norm_eps)
    kk = rmsnorm(rows_to_heads(h, lp["wk"], cfg.num_kv_heads), lp["k_norm"],
                 cfg.rms_norm_eps)
    vv = rows_to_heads(h, lp["wv"], cfg.num_kv_heads)
    q, kk = apply_rope(q[None], kk[None], positions[None], cfg.rope_theta)
    kc, vc = write_kv_paged(pool["k"], pool["v"], kk[0], vv, slots, positions,
                            layer_tables, prefill_tiles)
    o = ragged_pool_attention(q[0], kc, vc, slots, positions, layer_tables,
                              prefill_tiles).astype(h.dtype)
    return o.reshape(h.shape[0], -1) @ lp["wo"], {**pool, "k": kc, "v": vc}


def ragged_forward(cfg: Lfm2MoeConfig, params, tokens, slots, positions,
                   block_tables, cache, prefill_tiles=None):
    """Flat ragged step: [T] mixed tokens -> ([T, V] logits, cache). Each run
    of layers is scanned where its stack lies (``paged.scan_runs_paged``), a
    layer addressed in the leaves that count it: an attention layer through
    its block table, a convolution layer by its slots' rows."""
    from deepspeed_tpu.models.paged import SLOTS, scan_runs_paged

    scratch = cache[SLOTS]["conv"].shape[1] - 1

    def layer(op, stacks):
        def fn(x, lp, pool, address):
            h = rmsnorm(x, lp["norm"], cfg.rms_norm_eps)
            if op == "conv":
                o, state = shortconv.ragged(cfg, h, lp["mix"], pool[SLOTS],
                                            address, scratch, slots, positions,
                                            prefill_tiles)
                pool = {**pool, SLOTS: state}
            else:
                o, pool = attention_ragged(cfg, h, lp["mix"], pool, address,
                                           slots, positions, prefill_tiles)
            st = stacks and (*stacks, lp["ffn"]["first_expert"])
            return _ffn_sublayer(cfg, x + o, lp, routed_experts,
                                 stacked=st), pool

        return ("slot" if op == "conv" else "block"), fn

    runs = []
    for ((op, ffn), _), stack in zip(cfg.runs, params["runs"]):
        weights, stacks = (stack["ffn"], None) if ffn == "dense" \
            else expert_stacks(stack["ffn"])
        runs.append((*layer(op, stacks), {**stack, "ffn": weights}))
    x = params["embed"][tokens].astype(cache["k"].dtype)
    x, cache = scan_runs_paged(runs, x, cache, block_tables)
    return _head(cfg, params, x), cache


# ------------------------------------------------------------- arithmetic
def _layer_param_count(cfg: Lfm2MoeConfig, kind: tuple, experts) -> float:
    """One layer's parameters with ``experts`` routed experts counted."""
    op, ffn = kind
    d = cfg.hidden_size
    mixer = shortconv.mixer_param_count(cfg) if op == "conv" else \
        2 * d * cfg.head_dim * (cfg.num_heads + cfg.num_kv_heads) \
        + 2 * cfg.head_dim
    if ffn == "dense":
        return 2 * d + mixer + 3 * d * cfg.intermediate_size
    return (2 * d + mixer + d * cfg.num_experts + cfg.num_experts
            + 3 * experts * d * cfg.moe_intermediate_size)


def num_params(cfg: Lfm2MoeConfig) -> int:
    """Every parameter, the table once (the head is the table)."""
    return (cfg.vocab_size * cfg.hidden_size + cfg.hidden_size
            + sum(_layer_param_count(cfg, kind, cfg.num_experts)
                  for kind in cfg.kinds))


def flops_per_token(cfg: Lfm2MoeConfig, seq_len: int) -> float:
    """Active-parameter training FLOPs (``top_k`` experts a token and layer;
    the tied table once, as the head's product) plus attention over
    ``seq_len``; the convolution's taps are small beside its projections."""
    active = cfg.vocab_size * cfg.hidden_size + cfg.hidden_size + sum(
        _layer_param_count(cfg, kind, cfg.top_k) for kind in cfg.kinds)
    attn = (12.0 * cfg.layers_of("full_attention") * cfg.num_heads
            * cfg.head_dim * seq_len / 2.0)
    return 6.0 * active + attn


def build(cfg: Lfm2MoeConfig, ctx: ShardCtx | None = None) -> ModelSpec:
    ctx = ctx or ShardCtx()
    fwd = partial(forward, cfg, ctx=ctx)

    def loss_fn(params, batch, rng=None):
        del rng  # dropless routing draws nothing
        return causal_lm_loss(fwd(params, batch["input_ids"]),
                              batch["input_ids"], batch.get("labels"))

    return ModelSpec(
        name="lfm2_moe",
        config=cfg,
        init_fn=partial(init_params, cfg),
        loss_fn=loss_fn,
        forward_fn=fwd,
        param_logical_axes=param_logical_axes(cfg),
        logical_dim_units={"heads": cfg.num_heads,
                           "kv_heads": cfg.num_kv_heads,
                           "experts": cfg.num_experts},
        num_params=num_params(cfg),
        flops_per_token=partial(flops_per_token, cfg),
        init_paged_cache_fn=partial(init_paged_cache, cfg),
        ragged_forward_fn=partial(ragged_forward, cfg),
        supports_prefill_tiles=True,
        moe_form=partial(expert_form, num_experts=cfg.num_experts,
                         top_k=cfg.top_k),
        decode_bucket_min=DECODE_BUCKET_MIN,
        state_kind="shortconv",
    )
