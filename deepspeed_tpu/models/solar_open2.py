"""Solar-Open2 causal LM (``model_type: solar_open2``; Solar-Open2-250B
configures it): a gated grouped-query softmax layer WITHOUT positions, then
three Kimi Delta Attention layers (KDA with negative eigenvalues), twelve
times; sigmoid-routed experts beside one shared expert in every layer, no
leading dense layer.

The layers, as the published ``config.json`` gives them (``d`` hidden 4096; 48
layers, 0-indexed: ``G`` where ``i`` is in ``gqa_layers`` = 0, 4, .., 44, else
``K``). Pre-norm RMSNorm (``rms_norm_eps``), ``x <- x + mixer(norm(x))``, ``x
<- x + moe(norm(x))``, a final RMSNorm, an untied head::

    K, KDA(h):  models/kda.py's mixer at H = 64 heads, K = V = 128, P = 8192, conv kernel 4,
                beta = 2 sigmoid(h W_b) (kda_allow_neg_eigval: 1 - beta in (-1, 1));
                kda_use_full_proj false: the decay's and the gate's low-rank pairs, inner width 128

    G, GQA(h):  64 query heads over 8 K/V heads of 128, NO positions (use_rope false), no bias, no head norm
      q = h W_q -> [T, 64, 128];  k = h W_k -> [T, 8, 128];  v = h W_v -> [T, 8, 128]
      o = softmax_causal(q k^T * 128^-0.5) v  -> [T, 8192]
      out = (o * sigmoid(h W_g)) W_o                      # use_gqa_gate: W_g [4096, 8192], an element a head lane

    MoE(h):     s = sigmoid(h W_r) over 320 (float32);  pick = top8(s + e_score_correction_bias)
                w = s[pick] / sum(s[pick]) * routed_scaling_factor
                y = sum_i w_i SwiGLU_i(h) [1280] + SwiGLU_shared(h) [1280 x n_shared_experts]

**What is shared.** The KDA mixer is ``models/kda.py``'s, ``kimi_linear``'s
too: ``kda_beta_scale`` 2.0 is the one number that differs in its arithmetic
(and 64 heads where Kimi-Linear has 32). The ``G`` layer's serving path is
``paged.nope_attention_ragged``, ``granite_hybrid``'s and ``jamba``'s, handed
the gate: one fused multiply between the kernels' output and ``W_o``. The
expert layer is ``models/experts.routed_experts`` with the rank's ``held``
share, as ``deepseek``'s and ``kimi_linear``'s.

**The weights lie by their place in the layer scan** (``paged.stack_plan_tail``,
as ``kimi_linear``): ``params["lead"]``, ``params["period"]`` (one tree a
position of the repeated period, every leaf stacked ``[repeats, ...]``) and
``params["tail"]``. The published 48 layers are 12 x ``GKKK`` (four bodies a
step program); the benchmark's four, ``GKKK``, are a lead ``G`` and a scan
over three ``K`` (two bodies).

**Serving.** The ``G`` layers' K and V rows lie in block leaves ``cache["k"]``
/ ``["v"]`` ``[L_g, NB, BS, 8 x 128]``; the ``K`` layers' state lies beside
them in slot leaves (``kda.init_slot_leaves``): ``cache["slots"]["kda"]``
``[L_k, S, 128, 8192]`` float32, 4 MB a slot and layer, and ``["conv"]``, the
window leaf of the three convolutions' carried rows (24,576 channels are 192
lane tiles: ``[L_k, S, 48, 1536]`` in bfloat16). A KDA state beside K/V heads
is this family's; ``kimi_linear`` keeps its beside a latent row.

**One rank's share.** ``experts_held`` of the ``n_routed_experts`` routed
experts live here (``expert_rank``'s); the router scores and picks over all of
them. No code stands in for the other ranks or their exchange.

**Seeded weights** (``init_params``): std 0.02, output projections 0.02 /
sqrt(2 x layers), the KDA gates and convolutions by ``kda.draw``, the router's
selection bias N(0, 0.01), and ``W_g`` such that the gate's pre-activation
is about one wide (``GATE_PREACT_STD``): the ``G`` layer's gate is not a
constant 0.5.

**Assumed** (the catalog's row cannot confirm them; the benchmark's
configuration file has each with its reason): the gate's form (a sigmoid of a
FULL-width projection of the layer's normed input, on the heads' output
before ``W_o``: the published gated-attention form, and the one that brings
the whole model to 250.29 B parameters); the router (sigmoid scores, a
selection bias that does not enter the weights, no groups); the shared
expert's width ``moe_intermediate_size x n_shared_experts`` and SiLU gates;
KDA's forms as ``kimi_linear``'s; float32 state, bfloat16 convolution rows.
A quantized pool beside the slot state raises.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from deepspeed_tpu.models import kda
from deepspeed_tpu.models.api import ModelSpec, ShardCtx, causal_lm_loss
from deepspeed_tpu.models.experts import (
    expert_form,
    expert_stacks,
    routed_experts,
    routed_experts_einsum,
    swiglu,
)
from deepspeed_tpu.models.llama import rmsnorm
from deepspeed_tpu.models.paged import stack_plan_tail
from deepspeed_tpu.ops.attention import xla_attention

# ONE decode bucket at the benchmark's 16 slots: seven step programs of two
# layer bodies (a padding row reads and writes the scratch slot's 4 MB a KDA
# layer; the long-document pool keeps most of its 16 slots decoding or none)
DECODE_BUCKET_MIN = 16
# ``W_g``'s std: the gate's pre-activation ~N(0, 1) on a normed row of 4,096
# lanes (0.02 would give ~N(0, 0.16^2): a gate of 0.46-0.54 everywhere, which
# no check could tell from a constant)
GATE_PREACT_STD = 1.0


@dataclass(frozen=True)
class SolarOpen2Config:
    vocab_size: int = 196608
    hidden_size: int = 4096
    moe_intermediate_size: int = 1280     # one expert's FFN
    num_layers: int = 48
    gqa_layers: tuple | None = None       # 0-indexed; None: every fourth from 0
    num_heads: int = 64                   # the G layers' query heads
    num_kv_heads: int = 8
    head_dim: int = 128
    use_gqa_gate: bool = True
    use_rope: bool = False
    # the published group, whole: ``num_heads``, ``head_dim``,
    # ``short_conv_kernel_size``, ``num_kv_heads`` (null: a key head a query head)
    linear_attn_config: dict | None = None
    kda_allow_neg_eigval: bool = True
    kda_use_full_proj: bool = False
    num_experts: int = 320                # the routed experts the router scores
    num_shared_experts: int = 1
    top_k: int = 8
    first_k_dense: int = 0
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    experts_held: int | None = None       # of num_experts, those that live here
    expert_rank: int = 0                  # ... experts rank * held onwards
    rms_norm_eps: float = 1e-5
    chunk_size: int = 128                 # ``forward``'s chunk of the recurrence
    sub_chunk: int = 16                   # ``kda_tiles``' pairwise block
    max_seq_len: int = 1048576

    def __post_init__(self):
        gqa = self.gqa_layers
        if gqa is None:
            gqa = range(0, self.num_layers, 4)
        object.__setattr__(self, "gqa_layers", tuple(gqa))
        lin = self.linear_attn_config
        if lin is None:
            lin = {"num_heads": 64, "head_dim": 128,
                   "short_conv_kernel_size": 4, "num_kv_heads": None}
        if isinstance(lin, dict):  # a dict would make the config unhashable
            object.__setattr__(self, "linear_attn_config",
                               tuple(sorted(lin.items())))
        if not all(0 <= i < self.num_layers for i in self.gqa_layers):
            raise ValueError("solar_open2: gqa_layers must name layers 0 .. "
                             f"{self.num_layers - 1}")
        if self.use_rope or self.kda_use_full_proj or self.first_k_dense \
                or self._lin.get("num_kv_heads") is not None:
            raise NotImplementedError(
                "solar_open2: no positions, low-rank KDA gates, a key head a "
                "query head and experts in every layer, as published")
        if self.num_heads % self.num_kv_heads:
            raise ValueError("solar_open2: num_kv_heads must divide num_heads")
        if self.num_experts % self.held or not \
                0 <= self.expert_rank < self.num_experts // self.held:
            raise ValueError("solar_open2: experts_held must divide "
                             "num_experts and expert_rank name one of the "
                             "shares")
        if self.chunk_size % self.sub_chunk:
            raise ValueError("solar_open2: sub_chunk must divide chunk_size")
        stack_plan_tail(self.layer_pattern)  # raises what cannot be scanned

    # ---- what ``models/kda.py`` reads off a config
    @property
    def _lin(self) -> dict:
        return dict(self.linear_attn_config)

    @property
    def kda_heads(self) -> int:
        return self._lin["num_heads"]

    @property
    def kda_head_dim(self) -> int:
        return self._lin["head_dim"]

    @property
    def kda_width(self) -> int:
        """``P``: the lanes of each of q, k, v (and of a head's state row)."""
        return self.kda_heads * self.kda_head_dim

    @property
    def conv_kernel(self) -> int:
        return self._lin["short_conv_kernel_size"]

    @property
    def kda_beta_scale(self) -> float:
        """``beta`` in (0, 2) where the delta rule admits negative
        eigenvalues, in (0, 1) where not."""
        return 2.0 if self.kda_allow_neg_eigval else 1.0

    # ---- what ``paged.nope_attention_ragged`` reads
    q_scale = 1.0     # the scores' scale is the kernels' own head_dim ** -0.5

    @property
    def layer_pattern(self) -> str:
        gqa = set(self.gqa_layers)
        return "".join("G" if i in gqa else "K" for i in range(self.num_layers))

    def layers_of(self, kind: str) -> int:
        return self.layer_pattern.count(kind)

    @property
    def held(self) -> int:
        return self.num_experts if self.experts_held is None else self.experts_held

    @property
    def held_share(self):
        """``routed_experts``' ``held``; None where every expert lives here."""
        if self.held == self.num_experts:
            return None
        return (self.expert_rank * self.held, self.num_experts)

    @staticmethod
    def tiny(vocab_size: int = 256, pattern: str = "GKKK",
             **over) -> "SolarOpen2Config":
        """``pattern``'s layers (``G`` gated attention, ``K`` KDA): 4 query
        heads on 2 K/V heads of 16, 2 KDA heads of 16, sub-chunks of 4 in
        chunks of 8; 8 routed experts top-3, 4 of them held."""
        lin = {"num_heads": 2, "head_dim": 16, "short_conv_kernel_size": 4,
               "num_kv_heads": None}
        return SolarOpen2Config(**{**dict(
            vocab_size=vocab_size, hidden_size=64, moe_intermediate_size=48,
            num_layers=len(pattern),
            gqa_layers=tuple(i for i, c in enumerate(pattern) if c == "G"),
            num_heads=4, num_kv_heads=2, head_dim=16, linear_attn_config=lin,
            num_experts=8, top_k=3, experts_held=4, chunk_size=8, sub_chunk=4,
            max_seq_len=128), **over})


# ------------------------------------------------------------------ weights
def _mixer_shapes(cfg: SolarOpen2Config, kind: str) -> dict:
    """``{name: (shape, init)}`` of one mixer (``kda.mixer_shapes`` has the
    forms of ``init``; ``"gate"``: ``W_g``'s)."""
    if kind == "K":
        return kda.mixer_shapes(cfg)
    d, hd = cfg.hidden_size, cfg.head_dim
    shapes = {"wq": ((d, cfg.num_heads * hd), 0.02),
              "wk": ((d, cfg.num_kv_heads * hd), 0.02),
              "wv": ((d, cfg.num_kv_heads * hd), 0.02),
              "wo": ((cfg.num_heads * hd, d), "out")}
    if cfg.use_gqa_gate:
        shapes["w_g"] = ((d, cfg.num_heads * hd), "gate")
    return shapes


def _ffn_shapes(cfg: SolarOpen2Config) -> dict:
    d, fm = cfg.hidden_size, cfg.moe_intermediate_size
    e, held, fs = cfg.num_experts, cfg.held, cfg.num_shared_experts * fm
    return {"router": ((d, e), 0.02),
            # small and non-zero, so that selection (with the bias) and
            # weighting (without it) differ
            "router_bias": ((e,), 0.01),
            "w_gate": ((held, d, fm), 0.02), "w_up": ((held, d, fm), 0.02),
            "w_down": ((held, fm, d), "out"),
            "ws_gate": ((d, fs), 0.02), "ws_up": ((d, fs), 0.02),
            "ws_down": ((fs, d), "out")}


def _layer_shapes(cfg: SolarOpen2Config, kind: str) -> dict:
    d = cfg.hidden_size
    return {"attn_norm": ((d,), "ones"), "mlp_norm": ((d,), "ones"),
            "mix": _mixer_shapes(cfg, kind), "ffn": _ffn_shapes(cfg)}


def _is_shape(s) -> bool:
    return isinstance(s, tuple)


def init_params(cfg: SolarOpen2Config, rng) -> dict:
    """Seeded weights (module doc). The draws come from the device's own
    generator (``nemotron_h.init_params`` says why)."""
    lead, period, repeats, tail = stack_plan_tail(cfg.layer_pattern)
    rng = jax.random.wrap_key_data(jax.random.bits(rng, (4,), jnp.uint32),
                                   impl="rbg")
    draws = 2 + sum(init != "ones" for kind in lead + period + tail
                    for _, init in jax.tree_util.tree_leaves(
                        _layer_shapes(cfg, kind), is_leaf=_is_shape))
    # ONE split: a ``fold_in`` a draw costs this program seconds more to compile
    k = iter(jax.random.split(rng, draws))
    out_std = 0.02 / jnp.sqrt(2.0 * cfg.num_layers)
    gate_std = GATE_PREACT_STD * cfg.hidden_size ** -0.5

    def leaf(stack, shape, init):
        shape = stack + shape
        if init == "ones":
            return jnp.ones(shape, jnp.float32)
        if init in ("conv", "a", "dt"):
            return kda.draw(cfg, next(k), shape, init)
        std = {"out": out_std, "gate": gate_std}.get(init, init)
        return jax.random.normal(next(k), shape, jnp.float32) * std

    def layer(kind, stack=()):
        return jax.tree_util.tree_map(
            lambda s: leaf(stack, *s), _layer_shapes(cfg, kind),
            is_leaf=_is_shape)

    return {
        "embed": leaf((), (cfg.vocab_size, cfg.hidden_size), 0.02),
        "lead": [layer(kind) for kind in lead],
        "period": [layer(kind, (repeats,)) for kind in period],
        "tail": [layer(kind) for kind in tail],
        "final_norm": jnp.ones((cfg.hidden_size,), jnp.float32),
        "lm_head": leaf((), (cfg.hidden_size, cfg.vocab_size), 0.02),
    }


_AXES = {**kda.LOGICAL_AXES,
         "wq": ("embed", "heads"), "wk": ("embed", "kv_heads"),
         "wv": ("embed", "kv_heads"), "w_g": ("embed", "heads"),
         "router": ("embed", None),
         "w_gate": ("experts", "embed", "ffn"),
         "w_up": ("experts", "embed", "ffn"),
         "w_down": ("experts", "ffn", "embed"),
         "ws_gate": ("embed", "ffn"), "ws_up": ("embed", "ffn"),
         "ws_down": ("ffn", "embed"), "attn_norm": ("embed",),
         "mlp_norm": ("embed",)}


def param_logical_axes(cfg: SolarOpen2Config) -> dict:
    """The logical axes of ``init_params``' tree, leaf for leaf."""
    lead, period, _, tail = stack_plan_tail(cfg.layer_pattern)

    def layer(kind, stack=()):
        return jax.tree_util.tree_map_with_path(
            lambda path, s: stack + _AXES.get(path[-1].key,
                                              (None,) * len(s[0])),
            _layer_shapes(cfg, kind), is_leaf=_is_shape)

    return {
        "embed": ("vocab", "embed"),
        "lead": [layer(kind) for kind in lead],
        "period": [layer(kind, ("layers",)) for kind in period],
        "tail": [layer(kind) for kind in tail],
        "final_norm": ("embed",),
        "lm_head": ("embed", "vocab"),
    }


def _layer_weights(lp: dict, prepare) -> dict:
    """``prepare`` (a flat dict's just-in-time weight preparation) on the
    layer's two norms and on each of its two parts' trees."""
    return {**prepare({k: v for k, v in lp.items() if k not in ("mix", "ffn")}),
            "mix": prepare(lp["mix"]), "ffn": prepare(lp["ffn"])}


# ------------------------------------------------------------------ layers
def ffn_parts(cfg: SolarOpen2Config, h, lp, experts, **stacked):
    """``(routed, shared)`` of an expert layer on flat normed tokens ``h``
    [T, D], each [T, D]: what the held experts give and the shared expert. A
    rank's layer is their sum; the ranks of a deployment add their ``routed``
    parts and count ``shared`` once."""
    routed = experts(
        h, lp["router"], lp["w_gate"], lp["w_up"], lp["w_down"], cfg.top_k,
        **stacked, scoring="sigmoid", bias=lp["router_bias"],
        renormalize=cfg.norm_topk_prob, scale=cfg.routed_scaling_factor,
        eps=1e-20, held=cfg.held_share)
    return routed, swiglu(h, lp["ws_gate"], lp["ws_up"], lp["ws_down"])


def _gate(cfg: SolarOpen2Config, h, lp):
    """The ``G`` layer's output gate ``sigmoid(h W_g)`` [..., heads x
    head_dim] in ``h``'s dtype; None without ``use_gqa_gate``."""
    if not cfg.use_gqa_gate:
        return None
    return jax.nn.sigmoid((h @ lp["w_g"]).astype(jnp.float32)).astype(h.dtype)


def _attention_sequence(cfg: SolarOpen2Config, h, lp):
    """The ``G`` mixer over whole sequences ``h`` [B, S, D], no cache."""
    b, s = h.shape[:2]
    q, k, v = ((h @ lp[w]).reshape(b, s, heads, cfg.head_dim)
               for w, heads in (("wq", cfg.num_heads),
                                ("wk", cfg.num_kv_heads),
                                ("wv", cfg.num_kv_heads)))
    o = xla_attention(q, k, v, causal=True).reshape(b, s, -1)
    gate = _gate(cfg, h, lp)
    return (o if gate is None else o * gate) @ lp["wo"]


def _lm_head(head, x):
    from deepspeed_tpu.ops.quantizer import maybe_dequantize

    return x @ maybe_dequantize(head, x.dtype).astype(x.dtype)


def forward(cfg: SolarOpen2Config, params, input_ids,
            ctx: ShardCtx | None = None):
    """``[B, S]`` token ids -> ``[B, S, V]`` logits: the plain forward pass
    (no cache); the KDA layers in the chunk form, the experts through the
    einsum form."""
    ctx = ctx or ShardCtx()
    b, s = input_ids.shape
    lead, period, _, tail = stack_plan_tail(cfg.layer_pattern)
    x = ctx.embed_lookup(params["embed"], input_ids, "batch", "seq", "embed_act")

    def layer(kind, x, lp):
        lp = _layer_weights(lp, partial(ctx.layer_weights, dtype=x.dtype))
        h = rmsnorm(x, lp["attn_norm"], cfg.rms_norm_eps)
        if kind == "K":
            x = x + jax.vmap(partial(kda.sequence, cfg, lp["mix"]))(h)
        else:
            x = x + _attention_sequence(cfg, h, lp["mix"])
        h = rmsnorm(x, lp["mlp_norm"], cfg.rms_norm_eps)
        routed, shared = ffn_parts(cfg, h.reshape(b * s, -1), lp["ffn"],
                                   routed_experts_einsum)
        x = x + (routed + shared).reshape(x.shape)
        return ctx.constrain(x, "batch", "seq", "embed_act")

    def one_period(x, lps):
        for kind, lp in zip(period, lps):
            x = layer(kind, x, lp)
        return x, None

    for kind, lp in zip(lead, params["lead"]):
        x = layer(kind, x, lp)
    x, _ = lax.scan(one_period, x, tuple(params["period"]))
    for kind, lp in zip(tail, params["tail"]):
        x = layer(kind, x, lp)
    x = rmsnorm(x, params["final_norm"], cfg.rms_norm_eps)
    head = ctx.whole_weight(params["lm_head"], "lm_head")
    return ctx.constrain(_lm_head(head, x), "batch", "seq", "vocab_act")


# ------------------------------------------------------------------ inference
def init_paged_cache(cfg: SolarOpen2Config, num_blocks: int, block_size: int,
                     dtype=jnp.bfloat16, codec=None,
                     num_slots: int | None = None) -> dict:
    """The cache of the ragged engine (``models/paged.py``): the ``G`` layers'
    pool as block leaves, ``{"k", "v"}`` of ``[L_g, num_blocks, block_size,
    Hkv x D]``, and the ``K`` layers' state as slot leaves under ``"slots"``
    (``kda.init_slot_leaves``)."""
    from deepspeed_tpu.models.paged import SLOTS, init_paged_pool

    if codec is not None:
        raise NotImplementedError(
            "solar_open2: a quantized pool is not implemented beside slot "
            "state (the engine refuses it too)")
    if num_slots is None:
        raise ValueError("solar_open2: the cache needs the engine's slot "
                         "count (num_slots = max_seqs + 1) for its KDA state")
    cache = init_paged_pool(cfg.layers_of("G"), num_blocks, block_size,
                            cfg.num_kv_heads, cfg.head_dim, dtype)
    cache[SLOTS] = kda.init_slot_leaves(cfg, cfg.layers_of("K"), num_slots,
                                        dtype)
    return cache


def ragged_forward(cfg: SolarOpen2Config, params, tokens, slots, positions,
                   block_tables, cache, prefill_tiles=None):
    """Flat ragged step: [T] mixed tokens -> ([T, V] logits, cache). The
    leading layers run before a scan over the period and the tail after it
    (``models/paged.scan_layers_paged``), each layer addressed in the leaves
    that count it: a ``G`` layer through its block table, a ``K`` layer by
    its slots' rows."""
    from deepspeed_tpu.models.paged import (
        SLOTS,
        nope_attention_ragged,
        scan_layers_paged,
    )
    from deepspeed_tpu.ops.quantizer import dequantize_layer

    lead, period, _, tail = stack_plan_tail(cfg.layer_pattern)
    scratch = cache[SLOTS]["kda"].shape[1] - 1
    stacks, stacked = [], []
    for tree in params["period"]:
        ffn, st = expert_stacks(tree["ffn"])
        stacked.append({**tree, "ffn": ffn})
        stacks.append(st)

    def layer(kind, stack):
        def fn(x, lp, pool, address):
            lp = _layer_weights(lp, partial(dequantize_layer, dtype=x.dtype))
            h = rmsnorm(x, lp["attn_norm"], cfg.rms_norm_eps)
            if kind == "K":
                o, state = kda.ragged(cfg, h, lp["mix"], pool[SLOTS], address,
                                      scratch, slots, positions, prefill_tiles)
                pool = {**pool, SLOTS: state}
            else:
                o, pool = nope_attention_ragged(
                    cfg, h, lp["mix"], pool, address, slots, positions,
                    prefill_tiles, gate=_gate(cfg, h, lp["mix"]))
            x = x + o
            h = rmsnorm(x, lp["mlp_norm"], cfg.rms_norm_eps)
            ffn = lp["ffn"]
            st = None if stack is None else (*stack, ffn["first_expert"])
            routed, shared = ffn_parts(cfg, h, ffn, routed_experts, stacked=st)
            return x + (routed + shared).astype(x.dtype), pool

        return ("slot" if kind == "K" else "block"), fn

    x = params["embed"][tokens].astype(cache["k"].dtype)
    x, cache = scan_layers_paged(
        [layer(kind, st) for kind, st in zip(period, stacks)], x,
        tuple(stacked), cache, block_tables,
        lead=[(*layer(kind, None), lp) for kind, lp in zip(lead, params["lead"])],
        tail=[(*layer(kind, None), lp) for kind, lp in zip(tail, params["tail"])])
    x = rmsnorm(x, params["final_norm"], cfg.rms_norm_eps)
    return _lm_head(params["lm_head"], x), cache


# ------------------------------------------------------------- arithmetic
def _count(tree) -> int:
    total = 0
    for shape, _ in jax.tree_util.tree_leaves(tree, is_leaf=_is_shape):
        n = 1
        for dim in shape:
            n *= dim
        total += n
    return total


def _layer_param_count(cfg: SolarOpen2Config, kind: str, experts: float) -> float:
    """One layer's parameters with ``experts`` routed experts counted."""
    per_expert = 3 * cfg.hidden_size * cfg.moe_intermediate_size
    return _count(_layer_shapes(cfg, kind)) + (experts - cfg.held) * per_expert


def num_params(cfg: SolarOpen2Config) -> int:
    """Parameters that live here: the held experts, not all the routed."""
    return int(2 * cfg.vocab_size * cfg.hidden_size + cfg.hidden_size + sum(
        _layer_param_count(cfg, kind, cfg.held) for kind in cfg.layer_pattern))


def flops_per_token(cfg: SolarOpen2Config, seq_len: int) -> float:
    """Active-parameter training FLOPs of this rank's share (``top_k x held /
    num_experts`` experts a token and layer) plus the ``G`` layers' attention
    over ``seq_len``; the recurrence's own FLOPs are linear in the state and
    small beside the projections'."""
    active = cfg.vocab_size * cfg.hidden_size + sum(
        _layer_param_count(cfg, kind, cfg.top_k * cfg.held / cfg.num_experts)
        for kind in cfg.layer_pattern)
    attn = (12.0 * cfg.layers_of("G") * cfg.num_heads * cfg.head_dim
            * seq_len / 2.0)
    return 6.0 * active + attn


def build(cfg: SolarOpen2Config, ctx: ShardCtx | None = None) -> ModelSpec:
    ctx = ctx or ShardCtx()
    fwd = partial(forward, cfg, ctx=ctx)

    def loss_fn(params, batch, rng=None):
        del rng  # dropless routing draws nothing
        return causal_lm_loss(fwd(params, batch["input_ids"]),
                              batch["input_ids"], batch.get("labels"))

    return ModelSpec(
        name="solar_open2",
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
        state_kind=kda.STATE_KIND,
    )
