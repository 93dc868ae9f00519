"""Granite-4.0-H-family hybrid causal LM as ibm-granite/granite-4.0-h-small
configures it (``model_type: granitemoehybrid``): every layer is a MIXER, a
Mamba-2 layer or (one in ten) a grouped-query attention layer as the published
``layer_types`` says, and then an EXPERT BLOCK, routed experts beside a shared
MLP. ``nemotron_h``'s layer is one mixer; this family's is two sublayers, each
with its own RMSNorm and its own residual add scaled by ``residual_multiplier``.

- ``h = E[ids] * embedding_multiplier``.
- Every layer: ``h <- h + residual_multiplier * Mixer(RMSNorm(h))``, then ``h
  <- h + residual_multiplier * (MoE(x) + Shared(x))`` with ``x = RMSNorm(h)``.
- **attention.** Grouped-query, no bias, NO positional embedding
  (``position_embedding_type: "nope"``; the Mamba layers carry position):
  ``softmax(q k^T * attention_multiplier + causal) v``, then ``W_o``. The scale
  is ``attention_multiplier`` (1/128 as published), NOT ``head_dim ** -0.5``:
  the paged kernels and ``xla_attention`` scale by the latter, so ``q`` is
  multiplied by ``attention_multiplier * sqrt(head_dim)`` first.
- **mamba.** The Mamba-2 mixer of ``models/mamba2.py`` (shared with
  ``nemotron_h``), here at ``n_groups`` 1: one ``B`` and one ``C`` for all the
  heads, and the gated norm over all ``d_inner`` lanes.
- **experts.** ``l = x W_r`` over ALL the routed experts, the ``top_k`` largest
  picked, their weights the softmax over the picked logits in float32 (equal to
  the softmax over all, renormalised over the picks: Mixtral's rule, the
  defaults of ``models/experts.routed_experts``); expert ``e``: ``(silu(x
  W_gate,e) * (x W_up,e)) W_down,e``. The published checkpoint keeps ``[W_gate;
  W_up]`` as one ``input_linear`` of ``2 x intermediate_size`` rows; they are
  two stacks here, as the other families'. The shared MLP is the same form at
  ``shared_intermediate_size``, weight 1.
- ``logits = RMSNorm(h) E^T / logits_scaling``: the head is the embedding
  (``tie_word_embeddings``), read where it lies (a product over ``E``'s minor
  axis, no transpose of the table).

**One rank's share.** ``experts_held`` of the ``num_experts`` routed experts
live here, those of rank ``expert_rank``; the router scores and picks over all
of them and normalises over all its picks, and a layer computes the part its
own experts give (``routed_experts``' ``held``). ``vocab_size`` is the rows of
the table held here. No code stands in for the other ranks or their exchange.

**The stack.** ``layer_types`` is cut into RUNS of one kind (``m m m m m a m m
m m`` -> 5 mamba, 1 attention, 4 mamba) and the parameters are kept a run a
stack (``params["runs"]``), so that a step program scans each run where it lies
(``paged.scan_runs_paged``): three layer bodies for a period of ten, and none
of a run's weights is sliced out of a larger stack. The 40 published layers are
nine runs. ``paged.stack_plan``'s lead-and-period form would compile seven
bodies for one period (``mmmmma`` + ``m`` x 4) and ten for four; measured at
the benchmark's sizes on the chip it builds its programs in 108 s for 68 and
its decode step runs 21.06 ms for 20.15 (PERF.md section 6, PR 51).

**Serving.** The attention layers' K and V lie in the paged pool, the Mamba
layers' state beside it in slot leaves (``models/paged.py``,
``mamba2.init_slot_leaves``).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from itertools import accumulate, groupby

import jax
import jax.numpy as jnp

from deepspeed_tpu.models import mamba2
from deepspeed_tpu.models.api import ModelSpec, ShardCtx, causal_lm_loss
from deepspeed_tpu.models.experts import (
    expert_form,
    expert_stacks,
    routed_experts,
    routed_experts_einsum,
    swiglu,
)
from deepspeed_tpu.models.llama import rmsnorm
from deepspeed_tpu.ops.attention import xla_attention

# the seeded draw: a layer's projections back to the residual stream grow
# geometrically with depth, the last layer's this many times the first's
# (``init_params``)
STREAM_GROWTH = 256.0
KINDS = ("mamba", "attention")
PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4
DECODE_BUCKET_MIN = 128   # as nemotron_h's: a bucket is four step programs


@dataclass(frozen=True)
class GraniteHybridConfig:
    vocab_size: int = 100352
    hidden_size: int = 4096
    num_layers: int = 40
    layer_types: tuple = PERIOD * 4
    num_heads: int = 32
    num_kv_heads: int = 8
    mamba_num_heads: int = 128
    mamba_head_dim: int = 64
    n_groups: int = 1
    ssm_state_size: int = 128
    conv_kernel: int = 4
    # the tile of the plain forward pass's chunked form; the published
    # ``mamba_chunk_size`` (256) is its kernel's blocking and changes no result
    chunk_size: int = 128
    intermediate_size: int = 768          # ONE expert's width
    shared_intermediate_size: int = 1536
    num_experts: int = 72                 # the routed experts the router scores
    top_k: int = 10
    experts_held: int | None = None       # of them, those that live here
    expert_rank: int = 0                  # ... experts rank * held onwards
    embedding_multiplier: float = 12.0
    attention_multiplier: float = 0.0078125
    residual_multiplier: float = 0.22
    logits_scaling: float = 16.0
    rms_norm_eps: float = 1e-5
    # the seeded draw of dt (``mamba2.init_mixer``); not in the source's config
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    max_seq_len: int = 131072

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if len(self.layer_types) != self.num_layers \
                or set(self.layer_types) - set(KINDS):
            raise ValueError(
                "granite_hybrid: layer_types must name each of the "
                f"{self.num_layers} layers as one of {KINDS}")
        if self.hidden_size % self.num_heads \
                or self.num_heads % self.num_kv_heads \
                or self.mamba_num_heads % self.n_groups:
            raise ValueError(
                "granite_hybrid: num_heads must divide hidden_size, "
                "num_kv_heads num_heads and n_groups mamba_num_heads")
        held = self.held
        if self.num_experts % held \
                or not 0 <= self.expert_rank < self.num_experts // held:
            raise ValueError(
                "granite_hybrid: experts_held must divide num_experts and "
                "expert_rank name one of the shares")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @property
    def q_scale(self) -> float:
        """What ``q`` is multiplied by so that a kernel's ``head_dim ** -0.5``
        makes the scores' scale ``attention_multiplier``."""
        return self.attention_multiplier * self.head_dim ** 0.5

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
        return self.layer_types.count(kind)

    @property
    def runs(self) -> list:
        """``[(kind, layers)]``: ``layer_types`` as runs of one kind."""
        return [(kind, len(list(g))) for kind, g in groupby(self.layer_types)]

    @staticmethod
    def tiny(vocab_size: int = 256, layer_types=("mamba", "mamba", "attention",
                                                 "mamba", "mamba", "mamba"),
             **over) -> "GraniteHybridConfig":
        return GraniteHybridConfig(**{**dict(
            vocab_size=vocab_size, hidden_size=64, num_layers=len(layer_types),
            layer_types=layer_types, num_heads=4, num_kv_heads=2,
            mamba_num_heads=8, mamba_head_dim=16, ssm_state_size=16,
            chunk_size=8, intermediate_size=24, shared_intermediate_size=48,
            num_experts=12, top_k=4, experts_held=6, max_seq_len=128), **over})


def init_params(cfg: GraniteHybridConfig, rng) -> dict:
    """Seeded weights, a run of layers a stack (module doc). std 0.02; the
    Mamba draws are ``mamba2.init_mixer``'s. The device's own generator
    (``rbg``), as ``nemotron_h`` (threefry over one stack of experts costs the
    compiler seconds).

    The projections back to the residual stream are 0.02 / sqrt(2 x layers)
    in layer 0 and grow geometrically to ``STREAM_GROWTH`` times that in the
    last layer. Why: the head is the embedding and the stream starts as ``12
    E[token]``, so with every layer adding the same little, ``RMSNorm(h) .
    E[token]`` adds up coherently over the hidden size and the input token's
    OWN logit stands ~40 deviations above the rest whatever the layers
    compute: every served token repeats its input, and a check of served
    tokens sees no layer, no slot and no expert (ROADMAP B9). A trained
    model's layers write the next token's direction over the embedding's; a
    seeded one's can only outgrow it (at 256 the own logit is ~0.6 deviations;
    more growth leaves the logits to the last two layers alone, whose bfloat16
    error then shows undamped: 2,048 read 0.61 greedy agreement on the chip,
    PERF.md section 6, PR 51). The first layers still see the
    embedding beside their own output (``embedding_multiplier`` and
    ``residual_multiplier`` set that ratio), the last ones decide the
    logits."""
    d, f, fs = cfg.hidden_size, cfg.intermediate_size, cfg.shared_intermediate_size
    rng = jax.random.wrap_key_data(jax.random.bits(rng, (4,), jnp.uint32),
                                   impl="rbg")
    k_embed, *run_keys = jax.random.split(rng, 1 + len(cfg.runs))
    std = 0.02
    gain = jnp.geomspace(1.0, STREAM_GROWTH, cfg.num_layers) \
        * std / jnp.sqrt(2.0 * cfg.num_layers)

    def norm(key, *shape, s=std):
        return jax.random.normal(key, shape, jnp.float32) * s

    def run(kind: str, n: int, first: int, key) -> dict:
        k = iter(jax.random.split(key, 16))
        out_std = gain[first:first + n, None, None]
        if kind == "mamba":
            mix = mamba2.init_mixer(cfg, n, next(k), k, std, out_std)
        else:
            hq, hkv = (h * cfg.head_dim for h in (cfg.num_heads, cfg.num_kv_heads))
            mix = {"wq": norm(next(k), n, d, hq), "wk": norm(next(k), n, d, hkv),
                   "wv": norm(next(k), n, d, hkv),
                   "wo": norm(next(k), n, hq, d, s=out_std)}
        return {
            "norm": jnp.ones((n, d), jnp.float32),
            "mix": mix,
            "ffn_norm": jnp.ones((n, d), jnp.float32),
            "ffn": {
                "router": norm(next(k), n, d, cfg.num_experts),
                "w_gate": norm(next(k), n, cfg.held, d, f),
                "w_up": norm(next(k), n, cfg.held, d, f),
                "w_down": norm(next(k), n, cfg.held, f, d,
                               s=out_std[:, None]),
                "ws_gate": norm(next(k), n, d, fs),
                "ws_up": norm(next(k), n, d, fs),
                "ws_down": norm(next(k), n, fs, d, s=out_std),
            },
        }

    return {
        "embed": norm(k_embed, cfg.vocab_size, d),
        # a run's first layer: the layers of the runs before it
        "runs": [run(kind, n, first, key) for (kind, n), first, key in zip(
            cfg.runs, accumulate((n for _, n in cfg.runs), initial=0),
            run_keys)],
        "final_norm": jnp.ones((d,), jnp.float32),
    }


_ATTN_AXES = {
    "wq": ("layers", "embed", "heads"),
    "wk": ("layers", "embed", "kv_heads"),
    "wv": ("layers", "embed", "kv_heads"),
    "wo": ("layers", "heads", "embed"),
}
_FFN_AXES = {
    "router": ("layers", "embed", None),
    "w_gate": ("layers", "experts", "embed", "ffn"),
    "w_up": ("layers", "experts", "embed", "ffn"),
    "w_down": ("layers", "experts", "ffn", "embed"),
    "ws_gate": ("layers", "embed", "ffn"),
    "ws_up": ("layers", "embed", "ffn"),
    "ws_down": ("layers", "ffn", "embed"),
}


def param_logical_axes(cfg: GraniteHybridConfig) -> dict:
    return {
        "embed": ("vocab", "embed"),
        "runs": [{"norm": ("layers", "embed"),
                  "mix": mamba2.LOGICAL_AXES if kind == "mamba" else _ATTN_AXES,
                  "ffn_norm": ("layers", "embed"), "ffn": _FFN_AXES}
                 for kind, _ in cfg.runs],
        "final_norm": ("embed",),
    }


# ------------------------------------------------------------------ layers
def ffn_parts(cfg: GraniteHybridConfig, h, lp, experts, **stacked):
    """``(routed, shared)`` of an expert block on flat normed tokens ``h``
    [T, D], each [T, D]: what the held experts give and the shared MLP. A
    rank's block is their sum; the ranks of a deployment add their ``routed``
    parts and count ``shared`` once."""
    routed = experts(h, lp["router"], lp["w_gate"], lp["w_up"], lp["w_down"],
                     cfg.top_k, **stacked, held=cfg.held_share)
    return routed, swiglu(h, lp["ws_gate"], lp["ws_up"], lp["ws_down"])


def _ffn_sublayer(cfg: GraniteHybridConfig, x, lp, experts, **stacked):
    h = rmsnorm(x, lp["ffn_norm"], cfg.rms_norm_eps)
    y = sum(ffn_parts(cfg, h.reshape(-1, h.shape[-1]), lp["ffn"], experts,
                      **stacked))
    return x + y.reshape(x.shape).astype(x.dtype) * cfg.residual_multiplier


def _head(cfg: GraniteHybridConfig, params, x):
    """``RMSNorm(x) E^T / logits_scaling`` over the table's minor axis."""
    x = rmsnorm(x, params["final_norm"], cfg.rms_norm_eps)
    e = params["embed"].astype(x.dtype)
    return jnp.einsum("...d,vd->...v", x, e) / cfg.logits_scaling


def forward(cfg: GraniteHybridConfig, params, input_ids,
            ctx: ShardCtx | None = None):
    """``[B, S]`` token ids -> ``[B, S, V]`` logits: the plain forward pass
    (no cache), the layers in ``layer_types``' order; the Mamba layers in the
    chunked form, the experts through the einsum form."""
    ctx = ctx or ShardCtx()
    b, s = input_ids.shape
    x = ctx.embed_lookup(params["embed"], input_ids, "batch", "seq", "embed_act")
    x = x * cfg.embedding_multiplier
    for (kind, n), stack in zip(cfg.runs, params["runs"]):
        for i in range(n):
            lp = ctx.layer_weights(
                jax.tree_util.tree_map(lambda a: a[i], stack), x.dtype)  # noqa: B023
            h = rmsnorm(x, lp["norm"], cfg.rms_norm_eps)
            mix = lp["mix"]
            if kind == "mamba":
                o = jax.vmap(partial(mamba2.sequence, cfg, mix))(h)
            else:
                q, k, v = ((h @ mix[w]).reshape(b, s, heads, cfg.head_dim)
                           for w, heads in (("wq", cfg.num_heads),
                                            ("wk", cfg.num_kv_heads),
                                            ("wv", cfg.num_kv_heads)))
                o = xla_attention(q * cfg.q_scale, k, v, causal=True)
                o = o.reshape(b, s, -1) @ mix["wo"]
            x = x + o * cfg.residual_multiplier
            x = _ffn_sublayer(cfg, x, lp, routed_experts_einsum)
            x = ctx.constrain(x, "batch", "seq", "embed_act")
    return ctx.constrain(_head(cfg, params, x), "batch", "seq", "vocab_act")


# ------------------------------------------------------------------ inference
def init_paged_cache(cfg: GraniteHybridConfig, num_blocks: int, block_size: int,
                     dtype=jnp.bfloat16, codec=None,
                     num_slots: int | None = None) -> dict:
    """The cache of the ragged engine (``models/paged.py``): the attention
    layers' pool as block leaves, ``{"k", "v"}`` of ``[L_attention,
    num_blocks, block_size, Hkv x D]``, and the Mamba layers' state as slot
    leaves under ``"slots"`` (``mamba2.init_slot_leaves``)."""
    from deepspeed_tpu.models.paged import SLOTS, init_paged_pool

    if codec is not None:
        raise NotImplementedError(
            "granite_hybrid: a quantized pool is not implemented beside slot "
            "state (the engine refuses it too)")
    if num_slots is None:
        raise ValueError("granite_hybrid: the cache needs the engine's slot "
                         "count (num_slots = max_seqs + 1) for its Mamba state")
    cache = init_paged_pool(cfg.layers_of("attention"), num_blocks, block_size,
                            cfg.num_kv_heads, cfg.head_dim, dtype)
    cache[SLOTS] = mamba2.init_slot_leaves(cfg, cfg.layers_of("mamba"),
                                           num_slots, dtype)
    return cache


def ragged_forward(cfg: GraniteHybridConfig, params, tokens, slots, positions,
                   block_tables, cache, prefill_tiles=None):
    """Flat ragged step: [T] mixed tokens -> ([T, V] logits, cache). Each run
    of layers is scanned where its stack lies (``paged.scan_runs_paged``), a
    layer addressed in the leaves that count it: an attention layer through
    its block table, a Mamba layer by its slots' rows."""
    from deepspeed_tpu.models.paged import (
        SLOTS,
        nope_attention_ragged,
        scan_runs_paged,
    )

    scratch = cache[SLOTS]["ssm"].shape[1] - 1

    def layer(kind, stacks):
        def fn(x, lp, pool, address):
            h = rmsnorm(x, lp["norm"], cfg.rms_norm_eps)
            if kind == "mamba":
                o, state = mamba2.ragged(cfg, h, lp["mix"], pool[SLOTS],
                                         address, scratch, slots, positions,
                                         prefill_tiles)
                pool = {**pool, SLOTS: state}
            else:
                o, pool = nope_attention_ragged(cfg, h, lp["mix"], pool,
                                                address, slots, positions,
                                                prefill_tiles)
            x = x + o * cfg.residual_multiplier
            ffn = lp["ffn"]
            st = (*stacks, ffn["first_expert"]) if stacks is not None else None
            return _ffn_sublayer(cfg, x, lp, routed_experts, stacked=st), pool

        return ("slot" if kind == "mamba" else "block"), fn

    runs = []
    for (kind, _), stack in zip(cfg.runs, params["runs"]):
        ffn, stacks = expert_stacks(stack["ffn"])
        runs.append((*layer(kind, stacks), {**stack, "ffn": ffn}))
    x = (params["embed"][tokens] * cfg.embedding_multiplier).astype(
        cache["k"].dtype)
    x, cache = scan_runs_paged(runs, x, cache, block_tables)
    return _head(cfg, params, x), cache


# ------------------------------------------------------------- arithmetic
def _layer_param_count(cfg: GraniteHybridConfig, kind: str, experts) -> float:
    """One layer's parameters with ``experts`` routed experts counted."""
    d = cfg.hidden_size
    mixer = mamba2.mixer_param_count(cfg) if kind == "mamba" else \
        2 * d * cfg.head_dim * (cfg.num_heads + cfg.num_kv_heads)
    return (2 * d + mixer + d * cfg.num_experts
            + 3 * experts * d * cfg.intermediate_size
            + 3 * d * cfg.shared_intermediate_size)


def num_params(cfg: GraniteHybridConfig) -> int:
    """Parameters that live here: the held experts, the held rows of the
    table once (the head is the table)."""
    return (cfg.vocab_size * cfg.hidden_size + cfg.hidden_size
            + sum(_layer_param_count(cfg, kind, cfg.held)
                  for kind in cfg.layer_types))


def flops_per_token(cfg: GraniteHybridConfig, seq_len: int) -> float:
    """Active-parameter training FLOPs of this rank's share (``top_k x held
    / num_experts`` experts a token and layer; the tied table counts once,
    as the head's product) plus attention over ``seq_len``; the recurrence's
    own FLOPs are linear in the state and small beside the projections'."""
    active = cfg.vocab_size * cfg.hidden_size + sum(
        _layer_param_count(cfg, kind, cfg.top_k * cfg.held / cfg.num_experts)
        for kind in cfg.layer_types)
    attn = (12.0 * cfg.layers_of("attention") * cfg.num_heads * cfg.head_dim
            * seq_len / 2.0)
    return 6.0 * active + attn


def build(cfg: GraniteHybridConfig, ctx: ShardCtx | None = None) -> ModelSpec:
    ctx = ctx or ShardCtx()
    fwd = partial(forward, cfg, ctx=ctx)

    def loss_fn(params, batch, rng=None):
        del rng  # dropless routing draws nothing
        return causal_lm_loss(fwd(params, batch["input_ids"]),
                              batch["input_ids"], batch.get("labels"))

    return ModelSpec(
        name="granite_hybrid",
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
        state_kind="mamba2",
    )
