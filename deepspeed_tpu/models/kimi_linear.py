"""Kimi-Linear causal LM (``model_type: kimi_linear``;
Kimi-Linear-48B-A3B-Instruct configures it): Kimi Delta Attention (KDA, a
gated delta rule with a decay A CHANNEL) in three layers of four, latent
attention WITHOUT positions (MLA, NoPE) in the fourth, a leading dense layer
and sigmoid-routed experts beside one shared expert in every other.

The layers, as the published ``config.json`` gives them (``d`` hidden 2304; 27
layers, 1-indexed as ``linear_attn_config`` numbers them: KDA in
``kda_layers``, MLA in ``full_attn_layers``; layers ``1 .. first_k_dense`` end
in a dense SwiGLU of ``intermediate_size``, the others in the expert layer).
Pre-norm RMSNorm (``rms_norm_eps``), ``x <- x + mixer(norm(x))``, ``x <- x +
ffn(norm(x))``, a final RMSNorm, an untied head::

    KDA(h):   H = 32 heads, K = V = 128 (linear_attn_config.head_dim), P = H x 128, conv kernel 4
      q = silu(conv4(h W_q));  k = silu(conv4(h W_k));  v = silu(conv4(h W_v))     # causal depthwise, no bias
      q, k -> [T, H, K], each L2-normalised over K;  q *= K^-0.5;   v -> [T, H, V]
      g = -exp(A_log[head]) * softplus((h W_fa) W_fb + dt_bias)   -> [T, H, K]  (<= 0: a log-decay A CHANNEL)
      beta = sigmoid(h W_b)                                       -> [T, H]
      per head, S in R^{K x V}, float32, S_0 = 0 at position 0:
          S  <- diag(exp(g_t)) S                  # decay each of the K rows by its own factor
          u   = beta_t * (v_t - S^T k_t)          # the delta rule: what the decayed state does not yet say about k_t
          S  <- S + k_t u^T
          o_t = S^T q_t
      out = (RMSNorm_head(o) * sigmoid((h W_ga) W_gb)) W_o        # norm over a head's 128, weight [128]

    MLA(h):   models/deepseek.py's (q_lora_rank null), and NO rotation (mla_use_nope): the 64 "rope" lanes of the
              query and of the cached row stay as projected; scale (nope + rope)^-0.5

    MoE(h):   s = sigmoid(h W_r) over num_experts (float32);  pick = top_k(s + e_score_correction_bias)
              w = s[pick] / sum(s[pick]) * routed_scaling_factor;   y = sum_i w_i SwiGLU_i(h) + SwiGLU_shared(h)

**One KDA mixer** (``models/kda.py``, ``solar_open2``'s too): the equations
above at ``kda_beta_scale`` 1.0; the projections, the convolutions, the chunk
form, the decode row, the slot leaves and the gates' seeded draws are that
module's, and this family hands it the normed rows.

A layer is a mixer AND a feed-forward part, and the two vary independently:
``layer_pattern`` names each layer by one letter, ``D`` KDA + dense, ``K`` KDA +
experts, ``M`` MLA + experts, ``A`` MLA + dense (``DKKMKKKM...`` as published).
**One MLA and one expert layer**: the MLA sublayer is ``models/deepseek``'s
helpers (``_plain_attention``, ``_pool_attention``) under this config, the
feed-forward part its ``_ffn`` (``models/experts.routed_experts`` with the
rank's ``held`` share, as ``deepseek`` and ``nemotron_h``).

**The weights lie by their place in the layer scan**: ``params["lead"]`` (a
list, one tree a leading layer), ``params["period"]`` (one tree a position of
the repeated period, every leaf stacked ``[repeats, ...]``) and
``params["tail"]`` (what follows the last whole period: the published 27
layers are ``DK`` + 6 x ``KMKK`` + ``M``, seven layers to compile). A step
program's scan then hands every layer a slice of a stack that is nothing
else's, with no copy.

**Serving.** The MLA layers' rows ``[c, k_pe, zeros]`` (``row_lanes``, 640) lie
in the latent pool, ``cache["kv"]`` ``[L_mla, NB, BS, row_lanes]``; the KDA
layers' state lies beside it in slot leaves (``models/paged.py``):
``cache["slots"]["kda"]`` ``[L_kda, S, K, H x V]`` float32 (the key channels on
the sublanes, a head's values side by side on the lanes:
``ops/pallas/kda.py``) and ``["conv"]``, the last three rows of the three
convolutions' inputs ``[q | k | v]``, oldest first, as a window leaf
(``models/paged.py``: ``[L_kda, S, 3 x 16, 3 P / 16]`` in bfloat16, a row's
channels folded over a sublane tile's rows; ``[L_kda, S, 3, 3 P]`` for a width
the tile does not divide). ``models/kda.py`` says what a decode row and a
prefill tile do with them.

**One rank's share.** ``experts_held`` of the ``num_experts`` routed experts
live here (``expert_rank``'s); the router scores and picks over all of them.
No code stands in for the other ranks or their exchange.

Each departure from the published modelling code is under ``assumed`` in the
benchmark's configuration file. ``num_nextn_predict_layers`` is 0 as published.
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
from deepspeed_tpu.models.deepseek import (
    _ffn,
    _lm_head,
    _plain_attention,
    _pool_attention,
)
from deepspeed_tpu.models.experts import (
    expert_form,
    expert_stacks,
    routed_experts,
    routed_experts_einsum,
)
from deepspeed_tpu.models.llama import rmsnorm
from deepspeed_tpu.models.paged import stack_plan_tail

# ONE decode bucket at the benchmark's 128 slots: 7 step programs, not 27
# (``longcat_flash.DECODE_BUCKET_MIN`` has the argument; a padding row here
# reads and writes the scratch slot's 2 MB a KDA layer, 1.5% of a step a row)
DECODE_BUCKET_MIN = 128


@dataclass(frozen=True)
class KimiLinearConfig:
    vocab_size: int = 163840
    hidden_size: int = 2304
    intermediate_size: int = 9216         # the dense layers' FFN
    moe_intermediate_size: int = 1024     # one expert's FFN
    num_layers: int = 27
    # the published group, whole: ``kda_layers`` / ``full_attn_layers``
    # (1-indexed), ``head_dim``, ``num_heads``, ``short_conv_kernel_size``
    linear_attn_config: dict | None = None
    num_heads: int = 32                   # MLA's
    kv_lora_rank: int = 512
    q_lora_rank: int | None = None
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mla_use_nope: bool = True
    num_experts: int = 256                # the routed experts the router scores
    num_shared_experts: int = 1
    top_k: int = 8
    first_k_dense: int = 1
    moe_layer_freq: int = 1
    scoring_func: str = "sigmoid"
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.446
    n_group: int = 1
    topk_group: int = 1
    experts_held: int | None = None       # of num_experts, those that live here
    expert_rank: int = 0                  # ... experts rank * held onwards
    rope_theta: float = 10000.0           # read only with mla_use_nope off
    rms_norm_eps: float = 1e-5
    chunk_size: int = 128                 # ``forward``'s chunk of the recurrence
    sub_chunk: int = 16                   # ``kda_tiles``' pairwise block
    max_seq_len: int = 1048576

    def __post_init__(self):
        lin = self.linear_attn_config
        if lin is None:  # the published order, cut to ``num_layers``
            n = self.num_layers
            lin = {"kda_layers": [i for i in range(1, n + 1)
                                  if i % 4 and i != 27],
                   "full_attn_layers": [i for i in range(1, n + 1)
                                        if i % 4 == 0 or i == 27],
                   "head_dim": 128, "num_heads": 32,
                   "short_conv_kernel_size": 4}
        if isinstance(lin, dict):
            # a dict (of lists) would make the config unhashable
            lin = tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                               for k, v in lin.items()))
            object.__setattr__(self, "linear_attn_config", lin)
        lin = dict(lin)
        kda, full = set(lin["kda_layers"]), set(lin["full_attn_layers"])
        if kda & full or kda | full != set(range(1, self.num_layers + 1)):
            raise ValueError(
                "kimi_linear: linear_attn_config's kda_layers and "
                f"full_attn_layers must name each of layers 1 .. "
                f"{self.num_layers} once")
        if self.moe_layer_freq != 1 or self.n_group != 1 \
                or self.q_lora_rank is not None:
            raise NotImplementedError(
                "kimi_linear: moe_layer_freq 1, one routing group and a "
                "full-rank query, as published")
        if not 0 <= self.first_k_dense < self.num_layers:
            raise ValueError("kimi_linear: first_k_dense must leave at least "
                             "one expert layer")
        if self.num_experts % self.held or not \
                0 <= self.expert_rank < self.num_experts // self.held:
            raise ValueError("kimi_linear: experts_held must divide "
                             "num_experts and expert_rank name one of the "
                             "shares")
        if self.chunk_size % self.sub_chunk:
            raise ValueError("kimi_linear: sub_chunk must divide chunk_size")
        stack_plan_tail(self.layer_pattern)  # raises what cannot be scanned

    # ---- the linear-attention group
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

    kda_beta_scale = 1.0                  # ``beta`` in (0, 1) (``models/kda.py``)

    @property
    def layer_pattern(self) -> str:
        kda = set(self._lin["kda_layers"])
        return "".join(
            ("D" if i <= self.first_k_dense else "K") if i in kda
            else ("A" if i <= self.first_k_dense else "M")
            for i in range(1, self.num_layers + 1))

    def layers_of(self, kinds: str) -> int:
        return sum(self.layer_pattern.count(c) for c in kinds)

    # ---- what ``models/deepseek``'s MLA and FFN helpers read off a config
    yarn = None                           # no ``rope_scaling`` in the source
    mla_scale_q_lora = False
    mla_scale_kv_lora = False
    route_groups = None                   # one group

    @property
    def held(self) -> int:
        return self.num_experts if self.experts_held is None else self.experts_held

    @property
    def held_share(self):
        """``routed_experts``' ``held``; None where every expert lives here."""
        if self.held == self.num_experts:
            return None
        return (self.expert_rank * self.held, self.num_experts)

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def row_lanes(self) -> int:
        """Lanes of a cached row (``DeepseekConfig.row_lanes``)."""
        return -(-(self.kv_lora_rank + self.qk_rope_head_dim) // 128) * 128

    @property
    def softmax_scale(self) -> float:
        return self.qk_head_dim ** -0.5

    @staticmethod
    def tiny(vocab_size: int = 256, pattern: str = "DKMKM",
             **over) -> "KimiLinearConfig":
        """``pattern``'s layers (``K`` / ``D`` KDA, ``M`` / ``A`` MLA; the
        leading ``D`` / ``A`` dense), 2 KDA heads of 16, sub-chunks of 4 in
        chunks of 8; 8 routed experts top-3, 4 of them held."""
        n = len(pattern)
        dense = len(pattern) - len(pattern.lstrip("DA"))
        lin = {"kda_layers": [i + 1 for i in range(n) if pattern[i] in "DK"],
               "full_attn_layers": [i + 1 for i in range(n)
                                    if pattern[i] in "MA"],
               "head_dim": 16, "num_heads": 2, "short_conv_kernel_size": 4}
        return KimiLinearConfig(**{**dict(
            vocab_size=vocab_size, hidden_size=64, intermediate_size=96,
            moe_intermediate_size=48, num_layers=n, linear_attn_config=lin,
            num_heads=2, kv_lora_rank=32, qk_nope_head_dim=16,
            qk_rope_head_dim=16, v_head_dim=16, num_experts=8, top_k=3,
            first_k_dense=dense, experts_held=4, chunk_size=8, sub_chunk=4,
            max_seq_len=128), **over})


# ------------------------------------------------------------------ weights
def _mixer_shapes(cfg: KimiLinearConfig, is_kda: bool) -> dict:
    """``{name: (shape, init)}`` of one mixer (``kda.mixer_shapes`` has the
    forms of ``init``)."""
    if is_kda:
        return kda.mixer_shapes(cfg)
    d, heads, lat = cfg.hidden_size, cfg.num_heads, cfg.kv_lora_rank
    return {"wq": ((d, heads * cfg.qk_head_dim), 0.02),
            "wkv_a": ((d, lat + cfg.qk_rope_head_dim), 0.02),
            "kv_norm": ((lat,), "ones"),
            "wkv_b": ((lat, heads * (cfg.qk_nope_head_dim + cfg.v_head_dim)), 0.02),
            "wo": ((heads * cfg.v_head_dim, d), "out")}


def _ffn_shapes(cfg: KimiLinearConfig, dense: bool) -> dict:
    d, fm = cfg.hidden_size, cfg.moe_intermediate_size
    if dense:
        f = cfg.intermediate_size
        return {"w_gate": ((d, f), 0.02), "w_up": ((d, f), 0.02),
                "w_down": ((f, d), "out")}
    e, held, fs = cfg.num_experts, cfg.held, cfg.num_shared_experts * fm
    return {"router": ((d, e), 0.02),
            # small and non-zero, so that selection (with the bias) and
            # weighting (without it) differ
            "router_bias": ((e,), 0.01),
            "w_gate": ((held, d, fm), 0.02), "w_up": ((held, d, fm), 0.02),
            "w_down": ((held, fm, d), "out"),
            "ws_gate": ((d, fs), 0.02), "ws_up": ((d, fs), 0.02),
            "ws_down": ((fs, d), "out")}


def _layer_shapes(cfg: KimiLinearConfig, kind: str) -> dict:
    d = cfg.hidden_size
    return {"attn_norm": ((d,), "ones"), "mlp_norm": ((d,), "ones"),
            "mix": _mixer_shapes(cfg, kind in "DK"),
            "ffn": _ffn_shapes(cfg, kind in "DA")}


def init_params(cfg: KimiLinearConfig, rng) -> dict:
    """Seeded weights: std 0.02 (output projections 0.02 / sqrt(2 x layers)),
    the KDA gates and convolutions by ``kda.draw`` (``A_log = log U(1, 16)``
    a head, a channel's decay a token in ~[0.2, 0.999], the convolutions
    uniform in +-1 / sqrt(kernel)), ``e_score_correction_bias ~ N(0, 0.01)``: decay,
    ``beta`` and selection bias all matter from the first token. The draws
    come from the device's own generator (``nemotron_h.init_params`` says
    why)."""
    lead, period, repeats, tail = stack_plan_tail(cfg.layer_pattern)
    rng = jax.random.wrap_key_data(jax.random.bits(rng, (4,), jnp.uint32),
                                   impl="rbg")
    draws = 2 + sum(init != "ones" for kind in lead + period + tail
                    for _, init in jax.tree_util.tree_leaves(
                        _layer_shapes(cfg, kind),
                        is_leaf=lambda s: isinstance(s, tuple)))
    # ONE split: a ``fold_in`` a draw costs this program 9 s more to compile
    k = iter(jax.random.split(rng, draws))
    out_std = 0.02 / jnp.sqrt(2.0 * cfg.num_layers)

    def leaf(stack, shape, init):
        shape = stack + shape
        if init == "ones":
            return jnp.ones(shape, jnp.float32)
        if init in ("conv", "a", "dt"):
            return kda.draw(cfg, next(k), shape, init)
        std = out_std if init == "out" else init
        return jax.random.normal(next(k), shape, jnp.float32) * std

    def layer(kind, stack=()):
        return jax.tree_util.tree_map(
            lambda s: leaf(stack, *s), _layer_shapes(cfg, kind),
            is_leaf=lambda s: isinstance(s, tuple))

    return {
        "embed": leaf((), (cfg.vocab_size, cfg.hidden_size), 0.02),
        "lead": [layer(kind) for kind in lead],
        "period": [layer(kind, (repeats,)) for kind in period],
        "tail": [layer(kind) for kind in tail],
        "final_norm": jnp.ones((cfg.hidden_size,), jnp.float32),
        "lm_head": leaf((), (cfg.hidden_size, cfg.vocab_size), 0.02),
    }


_AXES = {**kda.LOGICAL_AXES,
         "wq": ("embed", "heads"), "wkv_a": ("embed", None),
         "wkv_b": (None, "heads"), "router": ("embed", None),
         "ws_gate": ("embed", "ffn"), "ws_up": ("embed", "ffn"),
         "ws_down": ("ffn", "embed"), "attn_norm": ("embed",),
         "mlp_norm": ("embed",)}
_EXPERT_AXES = {"w_gate": ("experts", "embed", "ffn"),
                "w_up": ("experts", "embed", "ffn"),
                "w_down": ("experts", "ffn", "embed")}
_DENSE_AXES = {"w_gate": ("embed", "ffn"), "w_up": ("embed", "ffn"),
               "w_down": ("ffn", "embed")}


def param_logical_axes(cfg: KimiLinearConfig) -> dict:
    """The logical axes of ``init_params``' tree, leaf for leaf."""
    lead, period, _, tail = stack_plan_tail(cfg.layer_pattern)

    def layer(kind, stack=()):
        def axes(tree, table):
            return {name: stack + table.get(name, (None,) * len(shape))
                    for name, (shape, _) in tree.items()}

        shapes = _layer_shapes(cfg, kind)
        ffn = {**_AXES, **(_DENSE_AXES if kind in "DA" else _EXPERT_AXES)}
        return {**axes({n: shapes[n] for n in ("attn_norm", "mlp_norm")}, _AXES),
                "mix": axes(shapes["mix"], _AXES),
                "ffn": axes(shapes["ffn"], ffn)}

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


def forward(cfg: KimiLinearConfig, params, input_ids,
            ctx: ShardCtx | None = None):
    """``[B, S]`` token ids -> ``[B, S, V]`` logits: the plain forward pass
    (no cache); the KDA layers in the chunk form, MLA as published (not
    absorbed), the experts through the einsum form."""
    ctx = ctx or ShardCtx()
    b, s = input_ids.shape
    lead, period, _, tail = stack_plan_tail(cfg.layer_pattern)
    x = ctx.embed_lookup(params["embed"], input_ids, "batch", "seq", "embed_act")
    positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))

    def layer(kind, x, lp):
        lp = _layer_weights(lp, partial(ctx.layer_weights, dtype=x.dtype))
        h = rmsnorm(x, lp["attn_norm"], cfg.rms_norm_eps)
        if kind in "DK":
            x = x + jax.vmap(partial(kda.sequence, cfg, lp["mix"]))(h)
        else:
            x = x + _plain_attention(cfg, h, lp["mix"], positions)
        h = rmsnorm(x, lp["mlp_norm"], cfg.rms_norm_eps)
        x = x + _ffn(cfg, h.reshape(b * s, -1), lp["ffn"],
                     routed_experts_einsum).reshape(x.shape)
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
def init_paged_cache(cfg: KimiLinearConfig, num_blocks: int, block_size: int,
                     dtype=jnp.bfloat16, codec=None,
                     num_slots: int | None = None) -> dict:
    """The cache of the ragged engine (``models/paged.py``): the MLA layers'
    latent pool as ONE block leaf, ``"kv"`` ``[L_mla, num_blocks, block_size,
    row_lanes]`` (a row ``[c, k_pe, zeros]``), and the KDA layers' state as
    slot leaves under ``"slots"``: ``kda`` ``[L_kda, num_slots, K, H x V]``
    float32 and ``conv``, the convolutions' ``kernel - 1`` carried rows of
    ``3 P`` channels, oldest first, as a window leaf
    (``paged.init_window_leaf``: ``[L_kda, num_slots, (kernel - 1) x r, 3 P /
    r]``). The last slot is the scratch slot."""
    from deepspeed_tpu.models.paged import SLOTS

    if codec is not None:
        raise NotImplementedError(
            "kimi_linear: a quantized pool is not implemented beside slot "
            "state (the engine refuses it too)")
    if num_slots is None:
        raise ValueError("kimi_linear: the cache needs the engine's slot "
                         "count (num_slots = max_seqs + 1) for its KDA state")
    return {
        "kv": jnp.zeros((cfg.layers_of("MA"), num_blocks, block_size,
                         cfg.row_lanes), dtype),
        SLOTS: kda.init_slot_leaves(cfg, cfg.layers_of("DK"), num_slots,
                                    dtype),
    }


def ragged_forward(cfg: KimiLinearConfig, params, tokens, slots, positions,
                   block_tables, cache, prefill_tiles=None):
    """Flat ragged step: [T] mixed tokens -> ([T, V] logits, cache). The
    leading layers run before a scan over the period and the tail after it
    (``models/paged.scan_layers_paged``), each layer addressed in the leaves
    that count it: an MLA layer through its block table, a KDA layer by its
    slots' rows."""
    from deepspeed_tpu.models.paged import SLOTS, scan_layers_paged
    from deepspeed_tpu.ops.quantizer import dequantize_layer

    lead, period, _, tail = stack_plan_tail(cfg.layer_pattern)
    scratch = cache[SLOTS]["kda"].shape[1] - 1
    stacks, stacked = [], []
    for kind, tree in zip(period, params["period"]):
        ffn, st = (tree["ffn"], None) if kind in "DA" else expert_stacks(
            tree["ffn"])
        stacked.append({**tree, "ffn": ffn})
        stacks.append(st)

    def layer(kind, stack):
        def fn(x, lp, pool, address):
            lp = _layer_weights(lp, partial(dequantize_layer, dtype=x.dtype))
            h = rmsnorm(x, lp["attn_norm"], cfg.rms_norm_eps)
            if kind in "DK":
                o, state = kda.ragged(cfg, h, lp["mix"], pool[SLOTS], address,
                                      scratch, slots, positions, prefill_tiles)
                pool = {**pool, SLOTS: state}
            else:
                o, kv = _pool_attention(cfg, h, lp["mix"], {"kv": pool["kv"]},
                                        positions, slots, address,
                                        prefill_tiles)
                pool = {**pool, **kv}
            x = x + o
            h = rmsnorm(x, lp["mlp_norm"], cfg.rms_norm_eps)
            ffn = lp["ffn"]
            st = (*stack, ffn["first_expert"]) if "first_expert" in ffn \
                and stack is not None else None
            return x + _ffn(cfg, h, ffn, routed_experts, stacked=st), pool

        return ("slot" if kind in "DK" else "block"), fn

    x = params["embed"][tokens].astype(cache["kv"].dtype)
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
    for shape, _ in jax.tree_util.tree_leaves(
            tree, is_leaf=lambda s: isinstance(s, tuple)):
        n = 1
        for dim in shape:
            n *= dim
        total += n
    return total


def _layer_param_count(cfg: KimiLinearConfig, kind: str, experts: float) -> float:
    """One layer's parameters with ``experts`` routed experts counted."""
    shapes = _layer_shapes(cfg, kind)
    n = _count(shapes)
    if kind in "KM":
        per_expert = 3 * cfg.hidden_size * cfg.moe_intermediate_size
        n += (experts - cfg.held) * per_expert
    return n


def num_params(cfg: KimiLinearConfig) -> int:
    """Parameters that live here: the held experts, not all the routed."""
    return int(2 * cfg.vocab_size * cfg.hidden_size + cfg.hidden_size + sum(
        _layer_param_count(cfg, kind, cfg.held) for kind in cfg.layer_pattern))


def flops_per_token(cfg: KimiLinearConfig, seq_len: int) -> float:
    """Active-parameter training FLOPs of this rank's share (``top_k x held /
    num_experts`` experts a token and layer) plus MLA's attention over
    ``seq_len``; the recurrence's own FLOPs are linear in the state and small
    beside the projections'."""
    active = cfg.vocab_size * cfg.hidden_size + sum(
        _layer_param_count(cfg, kind, cfg.top_k * cfg.held / cfg.num_experts)
        for kind in cfg.layer_pattern)
    attn = (6.0 * cfg.layers_of("MA") * cfg.num_heads
            * (cfg.qk_head_dim + cfg.v_head_dim) * seq_len / 2.0)
    return 6.0 * active + attn


def build(cfg: KimiLinearConfig, ctx: ShardCtx | None = None) -> ModelSpec:
    ctx = ctx or ShardCtx()
    fwd = partial(forward, cfg, ctx=ctx)

    def loss_fn(params, batch, rng=None):
        del rng  # dropless routing draws nothing
        return causal_lm_loss(fwd(params, batch["input_ids"]),
                              batch["input_ids"], batch.get("labels"))

    return ModelSpec(
        name="kimi_linear",
        config=cfg,
        init_fn=partial(init_params, cfg),
        loss_fn=loss_fn,
        forward_fn=fwd,
        param_logical_axes=param_logical_axes(cfg),
        logical_dim_units={"heads": cfg.num_heads, "experts": cfg.held},
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
