"""Jamba-family hybrid causal LM as ai21labs/AI21-Jamba2-3B configures it
(``model_type: jamba``): every layer is a MIXER, a Mamba-1 layer or (one in
``attn_layer_period``) a multi-query attention layer, and then a dense gated
MLP, each behind its own RMSNorm and its own plain residual add.

- ``h = E[ids]`` (no multiplier).
- Every layer: ``h <- h + Mixer(RMSNorm(h))``, then ``h <- h + W_down(silu(u
  W_gate) * (u W_up))`` with ``u = RMSNorm(h)``, no bias.
- **which mixer.** Layer ``i`` is attention iff ``i mod attn_layer_period ==
  attn_layer_offset`` (the family's published rule; 7 and 21 of 0-27 here),
  else Mamba. An FFN is routed iff ``num_experts > 1`` and ``i mod
  expert_layer_period == expert_layer_offset``; ``num_experts`` is 1 as
  published, so every FFN is the dense MLP (a routed one raises: not built).
- **attention.** Grouped-query at ``num_kv_heads`` K/V heads (ONE as
  published: multi-query, 20 query heads on it), no bias, NO positional term
  (the Mamba layers carry position): ``softmax(q k^T / sqrt(head_dim) +
  causal) v``, then ``W_o``.
- **mamba.** The Mamba-1 mixer of ``models/mamba1.py``: a decay a channel and
  state index, ``dt`` through a rank-``dt_rank`` bottleneck, ``dt`` / ``B`` /
  ``C`` each through an RMSNorm of its own.
- ``logits = RMSNorm(h) E^T``: the head is the embedding
  (``tie_word_embeddings``), read where it lies (a product over ``E``'s minor
  axis, no transpose of the table).

**The stack.** The layer kinds are cut into RUNS of one kind (7 Mamba, 1
attention, 13 Mamba, 1 attention, 6 Mamba as published) and the parameters are
kept a run a stack (``params["runs"]``), so that a step program scans each run
where it lies (``paged.scan_runs_paged``, as ``granite_hybrid``): five layer
bodies for 28 layers and none of a run's weights sliced out of a larger stack.

**Serving.** The attention layers' K and V lie in the paged pool (one row of
128 lanes a token and layer), the Mamba layers' state beside it in slot leaves
(``models/paged.py``, ``mamba1.init_slot_leaves``). The attention path is
``granite_hybrid``'s, at scale ``head_dim ** -0.5``.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from itertools import accumulate, groupby

import jax
import jax.numpy as jnp

from deepspeed_tpu.models import mamba1
from deepspeed_tpu.models.api import ModelSpec, ShardCtx, causal_lm_loss
from deepspeed_tpu.models.experts import swiglu
from deepspeed_tpu.models.llama import rmsnorm
from deepspeed_tpu.ops.attention import xla_attention

# the seeded draw: a layer's projections back to the residual stream grow
# geometrically with depth, the last layer's this many times the first's
# (``init_params``; ``JambaConfig.stream_growth``'s default)
STREAM_GROWTH = 1.0
KINDS = ("mamba", "attention")
# ONE decode bucket up to 256 slots: a bucket is four step programs of five
# layer bodies each, and with the ladder from 128 (two buckets, twelve
# programs) a cold run of the benchmark's cell that also met a void window
# took 365 s of the driver's 360 (PERF.md section 6, PR 53). What it costs: a
# padding row moves the scratch slot's state through ``selscan_decode`` like
# any row's (17 MB a row over the 26 layers, ~21 us), so an engine of 256 slots
# with 100 live rows pays ~3 ms of a 21 ms step for them (W8)
DECODE_BUCKET_MIN = 256


@dataclass(frozen=True)
class JambaConfig:
    vocab_size: int = 65536
    hidden_size: int = 2560
    num_layers: int = 28
    attn_layer_period: int = 14
    attn_layer_offset: int = 7
    expert_layer_period: int = 2
    expert_layer_offset: int = 1
    num_experts: int = 1
    top_k: int = 1
    num_heads: int = 20
    num_kv_heads: int = 1
    intermediate_size: int = 8192
    expand: int = 2
    ssm_state_size: int = 16
    dt_rank: int = 160
    conv_kernel: int = 4
    rms_norm_eps: float = 1e-6
    # the seeded draw of dt (``mamba1.init_mixer``); not in the source's config
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    # the seeded draw of the projections back to the stream (``init_params``)
    stream_growth: float = STREAM_GROWTH
    max_seq_len: int = 262144

    def __post_init__(self):
        if self.hidden_size % self.num_heads \
                or self.num_heads % self.num_kv_heads:
            raise ValueError("jamba: num_heads must divide hidden_size and "
                             "num_kv_heads num_heads")
        if not 0 <= self.attn_layer_offset < self.attn_layer_period:
            raise ValueError("jamba: attn_layer_offset must name a layer of "
                             "the period")
        if self.num_experts > 1:
            raise NotImplementedError(
                "jamba: routed FFNs (num_experts > 1: Jamba Mini / Large) "
                "are not built; AI21-Jamba2-3B's are all dense")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @property
    def q_scale(self) -> float:
        """``paged.nope_attention_ragged``'s factor on ``q``: none, the
        scores' scale is the kernels' own ``head_dim ** -0.5``."""
        return 1.0

    @property
    def d_inner(self) -> int:
        return self.expand * self.hidden_size

    @property
    def layer_types(self) -> tuple:
        return tuple(
            KINDS[i % self.attn_layer_period == self.attn_layer_offset]
            for i in range(self.num_layers))

    def layers_of(self, kind: str) -> int:
        return self.layer_types.count(kind)

    @property
    def runs(self) -> list:
        """``[(kind, layers)]``: ``layer_types`` as runs of one kind."""
        return [(kind, len(list(g))) for kind, g in groupby(self.layer_types)]

    @staticmethod
    def tiny(vocab_size: int = 256, **over) -> "JambaConfig":
        """Six layers, attention at layer 2: runs of 2, 1 and 3. At 64 lanes
        the tied head reads the input token back sqrt(64 / 2560) as strongly
        as at the published width, but six layers outgrow the table far less
        than 28: the growth is Granite's."""
        return JambaConfig(**{**dict(
            vocab_size=vocab_size, hidden_size=64, num_layers=6,
            attn_layer_period=4, attn_layer_offset=2, num_heads=4,
            num_kv_heads=1, intermediate_size=96, ssm_state_size=8,
            dt_rank=8, stream_growth=256.0, max_seq_len=128), **over})


def init_params(cfg: JambaConfig, rng) -> dict:
    """Seeded weights, a run of layers a stack (module doc). std 0.02; the
    Mamba draws are ``mamba1.init_mixer``'s. The device's own generator
    (``rbg``), as ``granite_hybrid``.

    The projections back to the residual stream (``W_out``, ``W_o``,
    ``W_down``) are 0.02 / sqrt(2 x layers) in layer 0 and grow geometrically
    to ``cfg.stream_growth`` times that in the last layer. Why: the head is the
    embedding and the stream starts as ``E[token]``, so whatever of it is left
    under the layers' outputs reads back as the input token's OWN logit, and a
    check of served tokens sees the layers only as far as they outgrow it
    (ROADMAP B9; ``granite_hybrid.init_params`` has the argument). There is no
    ``embedding_multiplier`` here, so the stream starts at the table's own
    0.02 and far less growth is needed than Granite's 256 (PERF.md section
    6, PR 53, has the readings)."""
    d, f = cfg.hidden_size, cfg.intermediate_size
    rng = jax.random.wrap_key_data(jax.random.bits(rng, (4,), jnp.uint32),
                                   impl="rbg")
    k_embed, *run_keys = jax.random.split(rng, 1 + len(cfg.runs))
    std = 0.02
    gain = jnp.geomspace(1.0, cfg.stream_growth, cfg.num_layers) \
        * std / jnp.sqrt(2.0 * cfg.num_layers)

    def norm(key, *shape, s=std):
        return jax.random.normal(key, shape, jnp.float32) * s

    def run(kind: str, n: int, first: int, key) -> dict:
        k = iter(jax.random.split(key, 16))
        out_std = gain[first:first + n, None, None]
        if kind == "mamba":
            mix = mamba1.init_mixer(cfg, n, k, std, out_std)
        else:
            hq, hkv = (h * cfg.head_dim for h in (cfg.num_heads, cfg.num_kv_heads))
            mix = {"wq": norm(next(k), n, d, hq), "wk": norm(next(k), n, d, hkv),
                   "wv": norm(next(k), n, d, hkv),
                   "wo": norm(next(k), n, hq, d, s=out_std)}
        return {
            "norm": jnp.ones((n, d), jnp.float32),
            "mix": mix,
            "ffn_norm": jnp.ones((n, d), jnp.float32),
            "ffn": {"w_gate": norm(next(k), n, d, f),
                    "w_up": norm(next(k), n, d, f),
                    "w_down": norm(next(k), n, f, d, s=out_std)},
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
    "w_gate": ("layers", "embed", "ffn"),
    "w_up": ("layers", "embed", "ffn"),
    "w_down": ("layers", "ffn", "embed"),
}


def param_logical_axes(cfg: JambaConfig) -> dict:
    return {
        "embed": ("vocab", "embed"),
        "runs": [{"norm": ("layers", "embed"),
                  "mix": mamba1.LOGICAL_AXES if kind == "mamba" else _ATTN_AXES,
                  "ffn_norm": ("layers", "embed"), "ffn": _FFN_AXES}
                 for kind, _ in cfg.runs],
        "final_norm": ("embed",),
    }


# ------------------------------------------------------------------ layers
def _ffn_sublayer(cfg: JambaConfig, x, lp):
    h = rmsnorm(x, lp["ffn_norm"], cfg.rms_norm_eps)
    ffn = lp["ffn"]
    return x + swiglu(h, ffn["w_gate"], ffn["w_up"], ffn["w_down"]).astype(x.dtype)


def _head(cfg: JambaConfig, params, x):
    """``RMSNorm(x) E^T`` over the table's minor axis."""
    x = rmsnorm(x, params["final_norm"], cfg.rms_norm_eps)
    return jnp.einsum("...d,vd->...v", x, params["embed"].astype(x.dtype))


def forward(cfg: JambaConfig, params, input_ids, ctx: ShardCtx | None = None):
    """``[B, S]`` token ids -> ``[B, S, V]`` logits: the plain forward pass
    (no cache), the layers in ``layer_types``' order; the Mamba layers token
    by token (``mamba1.sequence``)."""
    ctx = ctx or ShardCtx()
    b, s = input_ids.shape
    x = ctx.embed_lookup(params["embed"], input_ids, "batch", "seq", "embed_act")
    for (kind, n), stack in zip(cfg.runs, params["runs"]):
        for i in range(n):
            lp = ctx.layer_weights(
                jax.tree_util.tree_map(lambda a: a[i], stack), x.dtype)  # noqa: B023
            h = rmsnorm(x, lp["norm"], cfg.rms_norm_eps)
            mix = lp["mix"]
            if kind == "mamba":
                o = jax.vmap(partial(mamba1.sequence, cfg, mix))(h)
            else:
                q, k, v = ((h @ mix[w]).reshape(b, s, heads, cfg.head_dim)
                           for w, heads in (("wq", cfg.num_heads),
                                            ("wk", cfg.num_kv_heads),
                                            ("wv", cfg.num_kv_heads)))
                o = xla_attention(q, k, v, causal=True)
                o = o.reshape(b, s, -1) @ mix["wo"]
            x = _ffn_sublayer(cfg, x + o, lp)
            x = ctx.constrain(x, "batch", "seq", "embed_act")
    return ctx.constrain(_head(cfg, params, x), "batch", "seq", "vocab_act")


# ------------------------------------------------------------------ inference
def init_paged_cache(cfg: JambaConfig, num_blocks: int, block_size: int,
                     dtype=jnp.bfloat16, codec=None,
                     num_slots: int | None = None) -> dict:
    """The cache of the ragged engine (``models/paged.py``): the attention
    layers' pool as block leaves, ``{"k", "v"}`` of ``[L_attention,
    num_blocks, block_size, Hkv x D]``, and the Mamba layers' state as slot
    leaves under ``"slots"`` (``mamba1.init_slot_leaves``)."""
    from deepspeed_tpu.models.paged import SLOTS, init_paged_pool

    if codec is not None:
        raise NotImplementedError(
            "jamba: a quantized pool is not implemented beside slot state "
            "(the engine refuses it too)")
    if num_slots is None:
        raise ValueError("jamba: the cache needs the engine's slot count "
                         "(num_slots = max_seqs + 1) for its Mamba state")
    cache = init_paged_pool(cfg.layers_of("attention"), num_blocks, block_size,
                            cfg.num_kv_heads, cfg.head_dim, dtype)
    cache[SLOTS] = mamba1.init_slot_leaves(cfg, cfg.layers_of("mamba"),
                                           num_slots, dtype)
    return cache


def ragged_forward(cfg: JambaConfig, params, tokens, slots, positions,
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

    def layer(kind):
        def fn(x, lp, pool, address):
            h = rmsnorm(x, lp["norm"], cfg.rms_norm_eps)
            if kind == "mamba":
                o, state = mamba1.ragged(cfg, h, lp["mix"], pool[SLOTS],
                                         address, scratch, slots, positions,
                                         prefill_tiles)
                pool = {**pool, SLOTS: state}
            else:
                o, pool = nope_attention_ragged(cfg, h, lp["mix"], pool,
                                                address, slots, positions,
                                                prefill_tiles)
            return _ffn_sublayer(cfg, x + o, lp), pool

        return ("slot" if kind == "mamba" else "block"), fn

    runs = [(*layer(kind), stack)
            for (kind, _), stack in zip(cfg.runs, params["runs"])]
    x = params["embed"][tokens].astype(cache["k"].dtype)
    x, cache = scan_runs_paged(runs, x, cache, block_tables)
    return _head(cfg, params, x), cache


# ------------------------------------------------------------- arithmetic
def _layer_param_count(cfg: JambaConfig, kind: str) -> int:
    d = cfg.hidden_size
    mixer = mamba1.mixer_param_count(cfg) if kind == "mamba" else \
        2 * d * cfg.head_dim * (cfg.num_heads + cfg.num_kv_heads)
    return 2 * d + mixer + 3 * d * cfg.intermediate_size


def num_params(cfg: JambaConfig) -> int:
    """Every parameter, the table once (the head is the table)."""
    return (cfg.vocab_size * cfg.hidden_size + cfg.hidden_size
            + sum(_layer_param_count(cfg, kind) for kind in cfg.layer_types))


def flops_per_token(cfg: JambaConfig, seq_len: int) -> float:
    """Training FLOPs a token: every parameter (the tied table once, as the
    head's product) plus attention over ``seq_len``; the recurrence's own
    FLOPs are linear in the state and small beside the projections'."""
    attn = (12.0 * cfg.layers_of("attention") * cfg.num_heads * cfg.head_dim
            * seq_len / 2.0)
    return 6.0 * num_params(cfg) + attn


def build(cfg: JambaConfig, ctx: ShardCtx | None = None) -> ModelSpec:
    ctx = ctx or ShardCtx()
    fwd = partial(forward, cfg, ctx=ctx)

    def loss_fn(params, batch, rng=None):
        del rng
        return causal_lm_loss(fwd(params, batch["input_ids"]),
                              batch["input_ids"], batch.get("labels"))

    return ModelSpec(
        name="jamba",
        config=cfg,
        init_fn=partial(init_params, cfg),
        loss_fn=loss_fn,
        forward_fn=fwd,
        param_logical_axes=param_logical_axes(cfg),
        logical_dim_units={"heads": cfg.num_heads,
                           "kv_heads": cfg.num_kv_heads},
        num_params=num_params(cfg),
        flops_per_token=partial(flops_per_token, cfg),
        init_paged_cache_fn=partial(init_paged_cache, cfg),
        ragged_forward_fn=partial(ragged_forward, cfg),
        supports_prefill_tiles=True,
        decode_bucket_min=DECODE_BUCKET_MIN,
        state_kind="mamba1",
    )
