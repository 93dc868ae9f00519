"""Nemotron-H-family hybrid causal LM as NVIDIA-Nemotron-3-Super-120B-A12B
configures it (``model_type: nemotron_h``): a stack of Mamba-2 layers (``M``),
latent mixture-of-experts layers (``E``) and a few attention layers (``*``),
in the order of the published ``hybrid_override_pattern``.

Every layer is ``x <- x + mixer(RMSNorm(x))``; a final RMSNorm and an untied
head follow. No bias anywhere but the convolution's.

- **M, Mamba-2** (``models/mamba2.py``, shared with ``granite_hybrid``).
  ``[z | xBC | dt] = h W_in`` (widths ``d_inner`` | ``d_inner
  + 2 G N`` | ``H``); ``xBC <- silu(causal depthwise conv_K(xBC) + b)``; split
  ``x`` [H, P], ``B``, ``C`` [G, N] (``H / G`` heads share a group's);
  ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)`` a head; ``S_t = exp(dt_t
  A) S_{t-1} + dt_t x_t (x) B_t``, ``y_t = S_t C_t + D x_t``; ``y <-
  RMSNorm_grouped(y silu(z))`` (``G`` groups, its own weight); ``out = y
  W_out``. What a sequence carries from token to token is ``S`` (float32) and
  the last ``K - 1`` rows of ``xBC``, whatever its length.
- **\\*, attention.** Grouped-query attention, no bias, NO positional embedding
  (the Mamba layers carry position; the family's published modelling code
  never applies the config's ``rope_theta``).
- **E, LatentMoE.** The router scores the full hidden state in float32, ``s =
  sigmoid(h W_g)``; the ``top_k`` largest of ``s + e_score_correction_bias``
  are picked; their weights are ``s`` there, divided by their sum, times
  ``routed_scaling_factor``. ``u = h W_down`` (hidden -> latent); an expert is
  ungated, ``f_e(u) = relu(u W1_e)**2 W2_e``; ``y = (sum_picks w_e f_e(u))
  W_up + relu(h Ws1)**2 Ws2`` (the shared expert, on the full hidden state).
  The routed sum goes through ``models/experts.routed_experts``, as Mixtral's
  and DeepSeek's do.

**One rank's share.** ``experts_held`` of the ``num_experts`` routed experts
live here, those of rank ``expert_rank``; the router scores and picks over all
of them and normalises over all its picks, and a layer computes the part its
own experts give (``routed_experts``' ``held``). No code stands in for the
other ranks or their exchange.

**Serving.** The attention layers' K and V lie in the paged pool (``[L_attn,
NB, BS, Hkv x D]``); the Mamba layers' state lies beside it in slot leaves
(``models/paged.py``): ``ssm`` ``[L_mamba, S, N, H x P]`` float32 (the state
size first, a head's ``P`` values side by side on the lanes:
``ops/pallas/ssm.py`` says why) and ``conv``, the convolution's last ``K -
1`` input rows as a window leaf (``models/paged.py``: ``[L_mamba, S, (K - 1) x
16, conv width / 16]`` in bfloat16; ``[L_mamba, S, K - 1, conv width]`` for a
width the tile does not divide). A ragged step is decode rows, then prefill
tiles. A decode row is one update of its slot's state (``ssm_decode``). A tile
is one chunk of the chunked (SSD) form: matmuls inside the chunk, the state
carried from tile to tile of a slot in order, the first from the slot's state;
``dt = 0`` on a tile's rows past its valid ones, so that they neither decay nor
feed the state. A row or tile at position 0 starts from zeros whatever the slot
held. The stack runs as its leading layers and a scan over the period of the
pattern (``paged.stack_plan``): a step program compiles one period's body.

Multi-token prediction (``num_nextn_predict_layers``) is a drafting module
outside the forward pass and is not here. ``n_group > 1`` (group-limited
routing) and more than one shared expert raise.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

from deepspeed_tpu.models import mamba2
from deepspeed_tpu.models.api import ModelSpec, ShardCtx, causal_lm_loss
from deepspeed_tpu.models.experts import (
    expert_form,
    expert_stacks,
    routed_experts,
    routed_experts_einsum,
)
from deepspeed_tpu.models.llama import rmsnorm
from deepspeed_tpu.ops.attention import xla_attention

PUBLISHED_PATTERN = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
                     "EMEMEMEMEM*EMEMEMEMEM*EMEMEMEM*EMEMEMEME")
KINDS = {"M": "mamba", "E": "moe", "*": "attn"}
DECODE_BUCKET_MIN = 128


@dataclass(frozen=True)
class NemotronHConfig:
    vocab_size: int = 131072
    hidden_size: int = 4096
    num_layers: int = 88
    hybrid_override_pattern: str = PUBLISHED_PATTERN
    num_heads: int = 32
    num_kv_heads: int = 2
    head_dim: int = 128
    mamba_num_heads: int = 128
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    moe_latent_size: int = 1024
    moe_intermediate_size: int = 2688
    moe_shared_expert_intermediate_size: int = 5376
    n_shared_experts: int = 1
    num_experts: int = 512               # the routed experts the router scores
    top_k: int = 22
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 5.0
    n_group: int = 1
    experts_held: int | None = None      # of them, those that live here
    expert_rank: int = 0                 # ... experts rank * held onwards
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    rms_norm_eps: float = 1e-5
    max_seq_len: int = 262144

    def __post_init__(self):
        if len(self.hybrid_override_pattern) != self.num_layers \
                or set(self.hybrid_override_pattern) - set(KINDS):
            raise ValueError(
                "nemotron_h: hybrid_override_pattern must name each of the "
                f"{self.num_layers} layers as one of {sorted(KINDS)}")
        if self.n_group != 1:
            raise NotImplementedError(
                "nemotron_h: group-limited routing (n_group > 1) is not "
                "implemented; Nemotron-3-Super routes over one group")
        if self.n_shared_experts != 1:
            raise NotImplementedError(
                "nemotron_h: one shared expert of "
                "moe_shared_expert_intermediate_size, as published")
        if self.mamba_num_heads % self.n_groups:
            raise ValueError("nemotron_h: n_groups must divide mamba_num_heads")
        held = self.held
        if self.num_experts % held or not 0 <= self.expert_rank < self.num_experts // held:
            raise ValueError(
                "nemotron_h: experts_held must divide num_experts and "
                "expert_rank name one of the shares")

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
    def d_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_width(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.ssm_state_size

    def layers_of(self, kind: str) -> int:
        return self.hybrid_override_pattern.count(kind)

    @staticmethod
    def tiny(vocab_size: int = 256, pattern: str = "*EMEM",
             **over) -> "NemotronHConfig":
        return NemotronHConfig(**{**dict(
            vocab_size=vocab_size, hidden_size=64, num_layers=len(pattern),
            hybrid_override_pattern=pattern, num_heads=4, num_kv_heads=2,
            head_dim=16, mamba_num_heads=8, mamba_head_dim=16, n_groups=2,
            ssm_state_size=16, chunk_size=8, moe_latent_size=32,
            moe_intermediate_size=48, moe_shared_expert_intermediate_size=96,
            num_experts=16, top_k=6, experts_held=4, max_seq_len=128), **over})


def init_params(cfg: NemotronHConfig, rng) -> dict:
    d = cfg.hidden_size
    lat, f, fs = (cfg.moe_latent_size, cfg.moe_intermediate_size,
                  cfg.moe_shared_expert_intermediate_size)
    la, lm, le = (cfg.layers_of(c) for c in "*ME")
    # the draws come from the device's own generator ("rbg": one
    # RngBitGenerator instruction a draw). Threefry's rounds over ONE draw of
    # the experts' [5, 128, 1024, 2688] take the chip's compiler 17.5 s, the
    # whole tree 18.1 s; so 7.2 s (PERF.md section 6, PR 31)
    rng = jax.random.wrap_key_data(jax.random.bits(rng, (4,), jnp.uint32),
                                   impl="rbg")
    k = iter(jax.random.split(rng, 24))
    std = 0.02
    out_std = std / jnp.sqrt(2.0 * cfg.num_layers)

    def norm(key, *shape, s=std):
        return jax.random.normal(key, shape, jnp.float32) * s

    # drawn first, used by the Mamba layers' draws below (``mamba2.init_mixer``)
    k_dt = next(k)
    return {
        "embed": norm(next(k), cfg.vocab_size, d),
        "attn": {
            "norm": jnp.ones((la, d), jnp.float32),
            "wq": norm(next(k), la, d, cfg.num_heads * cfg.head_dim),
            "wk": norm(next(k), la, d, cfg.num_kv_heads * cfg.head_dim),
            "wv": norm(next(k), la, d, cfg.num_kv_heads * cfg.head_dim),
            "wo": norm(next(k), la, cfg.num_heads * cfg.head_dim, d, s=out_std),
        },
        "mamba": {
            "norm": jnp.ones((lm, d), jnp.float32),
            **mamba2.init_mixer(cfg, lm, k_dt, k, std, out_std),
        },
        "moe": {
            "norm": jnp.ones((le, d), jnp.float32),
            "router": norm(next(k), le, d, cfg.num_experts),
            # small and non-zero, so that selection (with the bias) and
            # weighting (without it) differ
            "router_bias": norm(next(k), le, cfg.num_experts, s=0.01),
            "w_lat_in": norm(next(k), le, d, lat),
            "w_lat_out": norm(next(k), le, lat, d, s=out_std),
            "w_up": norm(next(k), le, cfg.held, lat, f),
            "w_down": norm(next(k), le, cfg.held, f, lat, s=out_std),
            "ws_up": norm(next(k), le, d, fs),
            "ws_down": norm(next(k), le, fs, d, s=out_std),
        },
        "final_norm": jnp.ones((d,), jnp.float32),
        "lm_head": norm(next(k), d, cfg.vocab_size),
    }


PARAM_LOGICAL_AXES = {
    "embed": ("vocab", "embed"),
    "attn": {
        "norm": ("layers", "embed"),
        "wq": ("layers", "embed", "heads"),
        "wk": ("layers", "embed", "kv_heads"),
        "wv": ("layers", "embed", "kv_heads"),
        "wo": ("layers", "heads", "embed"),
    },
    "mamba": {
        "norm": ("layers", "embed"),
        **mamba2.LOGICAL_AXES,
    },
    "moe": {
        "norm": ("layers", "embed"),
        "router": ("layers", "embed", None),
        "router_bias": ("layers", None),
        "w_lat_in": ("layers", "embed", None),
        "w_lat_out": ("layers", None, "embed"),
        "w_up": ("layers", "experts", None, "ffn"),
        "w_down": ("layers", "experts", "ffn", None),
        "ws_up": ("layers", "embed", "ffn"),
        "ws_down": ("layers", "ffn", "embed"),
    },
    "final_norm": ("embed",),
    "lm_head": ("embed", "vocab"),
}


def _relu2(h, w_up, w_down):
    dtype = h.dtype
    return jnp.square(jax.nn.relu(h @ w_up.astype(dtype))) @ w_down.astype(dtype)


# ------------------------------------------------------------------ layers
def moe_parts(cfg: NemotronHConfig, h, lp, experts, **stacked):
    """``(routed, shared)`` of an expert layer on flat normed tokens ``h``
    [T, D], each [T, D]: what the held experts give, through the latent
    projections, and the shared expert. A rank's layer is their sum; the
    ranks of a deployment add their ``routed`` parts and count ``shared``
    once."""
    u = h @ lp["w_lat_in"].astype(h.dtype)
    routed = experts(
        u, lp["router"], None, lp["w_up"], lp["w_down"], cfg.top_k,
        **stacked, scoring="sigmoid", bias=lp["router_bias"],
        renormalize=cfg.norm_topk_prob, scale=cfg.routed_scaling_factor,
        eps=1e-20, held=cfg.held_share, router_h=h)
    return (routed @ lp["w_lat_out"].astype(h.dtype),
            _relu2(h, lp["ws_up"], lp["ws_down"]))


def _layer_params(params, kind: str, i: int):
    return jax.tree_util.tree_map(lambda a: a[i], params[KINDS[kind]])


def forward(cfg: NemotronHConfig, params, input_ids, ctx: ShardCtx | None = None):
    """``[B, S]`` token ids -> ``[B, S, V]`` logits: the plain forward pass
    (no cache), every layer in the pattern's order; the Mamba layers in the
    chunked form, the experts through the einsum form."""
    ctx = ctx or ShardCtx()
    b, s = input_ids.shape
    x = ctx.embed_lookup(params["embed"], input_ids, "batch", "seq", "embed_act")
    seen = {kind: 0 for kind in KINDS}
    for kind in cfg.hybrid_override_pattern:
        # a per-kind dict, not a slice of a stacked "layers" subtree: the
        # stage-3 gather hook (parallel/qwz.WeightGather) passes it through
        # and this family's training weights are left to the partitioner
        lp = ctx.layer_weights(_layer_params(params, kind, seen[kind]), x.dtype)
        seen[kind] += 1
        h = rmsnorm(x, lp["norm"], cfg.rms_norm_eps)
        if kind == "M":
            x = x + jax.vmap(partial(mamba2.sequence, cfg, lp))(h)
        elif kind == "E":
            x = x + sum(moe_parts(cfg, h.reshape(b * s, -1), lp,
                                  routed_experts_einsum)).reshape(x.shape)
        else:
            q, k, v = (
                (h @ lp[w]).reshape(b, s, n, cfg.head_dim) for w, n in
                (("wq", cfg.num_heads), ("wk", cfg.num_kv_heads),
                 ("wv", cfg.num_kv_heads)))
            o = xla_attention(q, k, v, causal=True)
            x = x + o.reshape(b, s, -1) @ lp["wo"]
        x = ctx.constrain(x, "batch", "seq", "embed_act")
    x = rmsnorm(x, params["final_norm"], cfg.rms_norm_eps)
    return ctx.constrain(x @ params["lm_head"].astype(x.dtype),
                         "batch", "seq", "vocab_act")


# ------------------------------------------------------------------ inference
def init_paged_cache(cfg: NemotronHConfig, num_blocks: int, block_size: int,
                     dtype=jnp.bfloat16, codec=None,
                     num_slots: int | None = None) -> dict:
    """The cache of the ragged engine (``models/paged.py``): the attention
    layers' pool as block leaves, ``{"k", "v"}`` of ``[L_attn, num_blocks,
    block_size, Hkv x D]``, and the Mamba layers' state as slot leaves under
    ``"slots"``: ``ssm`` ``[L_mamba, num_slots, N, H x P]`` float32 and
    ``conv``, the convolution's ``K - 1`` carried rows as a window leaf
    (``paged.init_window_leaf``: ``[L_mamba, num_slots, (K - 1) x r, conv
    width / r]``). The last slot is the scratch slot."""
    from deepspeed_tpu.models.paged import SLOTS, init_paged_pool

    if codec is not None:
        raise NotImplementedError(
            "nemotron_h: a quantized pool is not implemented beside slot "
            "state (the engine refuses it too)")
    if num_slots is None:
        raise ValueError("nemotron_h: the cache needs the engine's slot count "
                         "(num_slots = max_seqs + 1) for its Mamba state")
    lm = cfg.layers_of("M")
    cache = init_paged_pool(cfg.layers_of("*"), num_blocks, block_size,
                            cfg.num_kv_heads, cfg.head_dim, dtype)
    cache[SLOTS] = mamba2.init_slot_leaves(cfg, lm, num_slots, dtype)
    return cache


def _attn_ragged(cfg: NemotronHConfig, x, lp, pool, layer_tables, slots,
                 positions, prefill_tiles):
    from deepspeed_tpu.models.paged import (
        ragged_pool_attention,
        rows_to_heads,
        write_kv_paged,
    )

    t = x.shape[0]
    h = rmsnorm(x, lp["norm"], cfg.rms_norm_eps)
    q = rows_to_heads(h, lp["wq"], cfg.num_heads)
    kk = rows_to_heads(h, lp["wk"], cfg.num_kv_heads)
    vv = rows_to_heads(h, lp["wv"], cfg.num_kv_heads)
    kc, vc = write_kv_paged(pool["k"], pool["v"], kk, vv, slots, positions,
                            layer_tables, prefill_tiles)
    o = ragged_pool_attention(q, kc, vc, slots, positions, layer_tables,
                              prefill_tiles).astype(x.dtype)
    return x + o.reshape(t, -1) @ lp["wo"], {**pool, "k": kc, "v": vc}


def _moe_ragged(cfg: NemotronHConfig, x, lp, stacks):
    h = rmsnorm(x, lp["norm"], cfg.rms_norm_eps)
    stacked = (*stacks, lp["first_expert"]) if "first_expert" in lp else None
    return x + sum(moe_parts(cfg, h, lp, routed_experts, stacked=stacked))


def _period_stack(tree, lead: int, per: int, j: int, repeats: int):
    """Position ``j`` of a period's ``per`` layers of one kind, over the
    ``repeats`` of the scan, out of the kind's stacked ``tree`` whose first
    ``lead`` layers lead. The whole stack where that is what it is (one
    layer of the kind a period, none leading): anything else is a copy."""
    if lead == 0 and per == 1:
        return tree
    return jax.tree_util.tree_map(
        lambda a: a[lead:].reshape((repeats, per) + a.shape[1:])[:, j], tree)


def ragged_forward(cfg: NemotronHConfig, params, tokens, slots, positions,
                   block_tables, cache, prefill_tiles=None):
    """Flat ragged step: [T] mixed tokens -> ([T, V] logits, cache). The
    leading layers of the pattern run before a scan over its period
    (``models/paged.scan_layers_paged``), each layer addressed in the leaves
    that count it: an attention layer through its block table, a Mamba layer
    by its slots' rows."""
    from deepspeed_tpu.models.paged import SLOTS, scan_layers_paged, stack_plan

    lead, period, repeats = stack_plan(cfg.hybrid_override_pattern)
    scratch = cache[SLOTS]["ssm"].shape[1] - 1
    moe, stacks = expert_stacks(params["moe"])
    trees = {"*": params["attn"], "M": params["mamba"], "E": moe}

    def attn(x, lp, pool, layer_tables):
        return _attn_ragged(cfg, x, lp, pool, layer_tables, slots, positions,
                            prefill_tiles)

    def mamba(x, lp, pool, slot0):
        o, state = mamba2.ragged(
            cfg, rmsnorm(x, lp["norm"], cfg.rms_norm_eps), lp, pool[SLOTS],
            slot0, scratch, slots, positions, prefill_tiles)
        return x + o, {**pool, SLOTS: state}

    def moe_layer(x, lp, pool, _):
        return _moe_ragged(cfg, x, lp, stacks), pool

    fns = {"*": ("block", attn), "M": ("slot", mamba), "E": (None, moe_layer)}
    seen = {kind: 0 for kind in KINDS}
    leading = []
    for kind in lead:
        leading.append((*fns[kind], jax.tree_util.tree_map(
            lambda a: a[seen[kind]], trees[kind])))  # noqa: B023
        seen[kind] += 1
    at = {kind: 0 for kind in KINDS}
    stacked = []
    for kind in period:
        stacked.append(_period_stack(trees[kind], seen[kind],
                                     period.count(kind), at[kind], repeats))
        at[kind] += 1
    x = params["embed"][tokens].astype(cache["k"].dtype)
    x, cache = scan_layers_paged([fns[kind] for kind in period], x,
                                 tuple(stacked), cache, block_tables,
                                 lead=leading)
    x = rmsnorm(x, params["final_norm"], cfg.rms_norm_eps)
    return x @ params["lm_head"].astype(x.dtype), cache


# ------------------------------------------------------------- arithmetic
def _layer_param_count(cfg: NemotronHConfig, kind: str, experts: int) -> int:
    d = cfg.hidden_size
    if kind == "M":
        return d + mamba2.mixer_param_count(cfg)
    if kind == "*":
        return d + 2 * d * cfg.head_dim * (cfg.num_heads + cfg.num_kv_heads)
    lat, f = cfg.moe_latent_size, cfg.moe_intermediate_size
    return (d + d * cfg.num_experts + cfg.num_experts + 2 * d * lat
            + 2 * experts * lat * f
            + 2 * d * cfg.moe_shared_expert_intermediate_size)


def num_params(cfg: NemotronHConfig) -> int:
    """Parameters that live here: the held experts, not all the routed."""
    return (2 * cfg.vocab_size * cfg.hidden_size + cfg.hidden_size
            + sum(_layer_param_count(cfg, kind, cfg.held)
                  for kind in cfg.hybrid_override_pattern))


def flops_per_token(cfg: NemotronHConfig, seq_len: int) -> float:
    """Active-parameter training FLOPs of this rank's share (``top_k x held
    / num_experts`` experts a token and layer) plus attention over
    ``seq_len``; the recurrence's own FLOPs are linear in the state and
    small beside the projections'."""
    active = (cfg.vocab_size * cfg.hidden_size + sum(
        _layer_param_count(cfg, kind, 0)
        for kind in cfg.hybrid_override_pattern)
        + cfg.layers_of("E") * cfg.top_k * cfg.held / cfg.num_experts
        * 2 * cfg.moe_latent_size * cfg.moe_intermediate_size)
    attn = (12.0 * cfg.layers_of("*") * cfg.num_heads * cfg.head_dim
            * seq_len / 2.0)
    return 6.0 * active + attn


def build(cfg: NemotronHConfig, ctx: ShardCtx | None = None) -> ModelSpec:
    from deepspeed_tpu.models.paged import stack_plan

    ctx = ctx or ShardCtx()
    stack_plan(cfg.hybrid_override_pattern)  # raises what cannot be scanned
    fwd = partial(forward, cfg, ctx=ctx)

    def loss_fn(params, batch, rng=None):
        del rng  # dropless routing draws nothing
        return causal_lm_loss(fwd(params, batch["input_ids"]),
                              batch["input_ids"], batch.get("labels"))

    return ModelSpec(
        name="nemotron_h",
        config=cfg,
        init_fn=partial(init_params, cfg),
        loss_fn=loss_fn,
        forward_fn=fwd,
        param_logical_axes=PARAM_LOGICAL_AXES,
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
        state_kind="mamba2",
    )
