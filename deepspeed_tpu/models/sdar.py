"""SDAR-family sparse-MoE LM that generates by diffusion over blocks
(JetLM SDAR-30B-A3B-Chat, ``model_type: sdar_moe``).

The layer is Mixtral's (pre-norm, GQA, RoPE over all lanes in half-split
pairs, a softmax router whose top-k weights are renormalised, SiLU-gated
experts) with an explicit ``head_dim`` (128, not ``hidden / heads`` = 64), an
RMSNorm over every head's ``q`` and ``k`` before the rotation (one ``[128]``
gain each a layer) and 128 small experts, top-8; the serving path runs
``mixtral._ragged_layer`` with those two flags. What is new is how it
generates (``ModelSpec.block_gen``):

- **Block-causal attention.** With blocks of ``B`` positions a query at ``i``
  sees keys ``j <= (i | (B - 1))``: every earlier block and its own block
  whole. A prompt's whole blocks are prefilled under that mask.
- **Unshifted logits.** Row ``i`` scores the token AT position ``i``, so a
  position that is still masked (embedded as ``mask_token_id``) predicts
  itself from the context and from what of its own block is known.
- **A block is denoised in place, then committed.** A new block at ``p0``
  starts as the prompt's remainder, if any, then ``MASK``; each of ``T``
  denoise passes runs the block's ``B`` rows against the cache below ``p0``
  and each other, picks ``argmax`` at the masked positions and unmasks ``B /
  T`` of them (``remask``: ``"sequential"`` the leftmost,
  ``"low_confidence_static"`` those whose pick is most probable); a position
  once unmasked never changes. One more pass over the finished block writes
  its K and V (the commit) and ``p0`` moves on.

``forward`` is the full-sequence form under the same mask, with the
two-stream replay (``masked=``) a test teacher-forces a served trajectory
with; ``ragged_forward`` is one engine step. The seeded weights
(``init_params``) are drawn so that a comparison of logits can see all of
this: see the gains below.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

from deepspeed_tpu.models.api import (
    BlockGen,
    ModelSpec,
    ShardCtx,
    causal_lm_loss,
)
from deepspeed_tpu.models.experts import (
    expert_form,
    expert_stacks,
    routed_experts_einsum,
)
from deepspeed_tpu.models.llama import rmsnorm
from deepspeed_tpu.models.mixtral import _ragged_layer
from deepspeed_tpu.ops.attention import apply_rope, xla_attention

# The seeded weights: every matrix N(0, gain / sqrt(rows it sums over)), the
# embedding's rows N(0, 1), as ``smallthinker``'s and for its reason (under
# the other families' N(0, 0.02) draw random layers put one vector on every
# row and greedy decoding repeats one token, so no fault moves a pick). Here
# the scores' width is set by the q/k norm's gains, not by ``wq`` / ``wk``:
# a normed head is a unit-RMS vector times its gain, so a query's scores
# over random keys are ``mean(g_q g_k)`` wide. ``QK_SCORE_STD`` 3.0: one to
# three keys hold a row's weight, and the rotation tells the four rows of a
# block apart (a block of identical ``MASK`` embeddings differs ONLY by
# position: at softer scores its four rows read the same mixture and pick the
# same token, and neither the in-block mask nor the pass a position was
# unmasked in could be seen in a pick). The gains are drawn, not constant:
# ``QK_GAIN_SPREAD`` is the log-normal width a lane, so a program that left
# the gains out, or applied q's to k, reads other scores. ``ATTN_OUT_GAIN`` /
# ``EXPERT_OUT_GAIN``: what a layer's attention and its eight picked experts
# add to a unit row. ``QK_SHARE``: a query head's projection is that much its
# KV head's and the rest its own draw, so a row's score with a row of LIKE
# content nearby is ``34 x QK_SHARE`` over the random keys' 3.0 (the rotation
# wears it off slowly with the distance), as a trained head attends to what
# resembles its query. Without it (0.0) a masked row's pick hardly depends on
# whether its left neighbour in the block is a token yet or still the mask
# (one key among ~1,300), and a replay at ANOTHER number of passes a block
# agreed with what was served as well as the right one did (0.926 against
# 0.934, PERF.md section 6, PR 47, read on the chip): the check could not see
# the schedule. At 0.3 the masked rows of a block read each other about as
# much as their best context key, the replay at T = 4 of a T = 2 run reads
# 0.83 against 0.96, and 27% of the blocks come out as one token four times
# (14% at 0.0); a shared vector in every embedding row instead (a score that
# depends on the distance alone) collapsed 91% of them.
QK_SCORE_STD = 3.0
QK_GAIN_SPREAD = 0.2
QK_SHARE = 0.3
ATTN_OUT_GAIN = 0.6
EXPERT_OUT_GAIN = 0.3


@dataclass(frozen=True)
class SdarConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    moe_intermediate_size: int = 768
    num_layers: int = 48
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128
    num_experts: int = 128
    top_k: int = 8
    rope_theta: float = 1000000.0
    rms_norm_eps: float = 1e-6
    max_seq_len: int = 32768
    # generation (the release's generate.py; the catalog row gives none)
    block_length: int = 4
    denoise_steps: int = 2
    remask: str = "sequential"
    mask_token_id: int = 151669

    def __post_init__(self):
        self.block_gen  # refuses what is not built, in BlockGen's words
        if not 0 <= self.mask_token_id < self.vocab_size:
            raise ValueError(f"mask_token_id {self.mask_token_id} is outside "
                             f"the vocabulary of {self.vocab_size}")

    @property
    def hd(self) -> int:
        return self.head_dim

    @property
    def block_gen(self) -> BlockGen:
        return BlockGen(self.block_length, self.denoise_steps, self.remask,
                        self.mask_token_id)

    @staticmethod
    def tiny(vocab_size: int = 256, **more) -> "SdarConfig":
        """Two layers, 8 experts top-2, head 16 of a hidden 32 (so ``head_dim
        != hidden / heads``), blocks of four; the mask token is the
        vocabulary's last."""
        return SdarConfig(**{**dict(
            vocab_size=vocab_size, hidden_size=32, moe_intermediate_size=16,
            num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
            num_experts=8, top_k=2, max_seq_len=128,
            mask_token_id=vocab_size - 1), **more})


def _layer_shapes(cfg: SdarConfig) -> dict:
    """``{name: (shape, gain)}`` of one layer; gain None: a norm's ones,
    ``"qk"``: a head norm's drawn gains."""
    d, f, hd = cfg.hidden_size, cfg.moe_intermediate_size, cfg.head_dim
    hq, hkv, e = cfg.num_heads, cfg.num_kv_heads, cfg.num_experts
    return {
        "attn_norm": ((d,), None),
        "wq": ((d, hq * hd), 1.0),
        "wk": ((d, hkv * hd), 1.0),
        "wv": ((d, hkv * hd), 1.0),
        "q_norm": ((hd,), "qk"),
        "k_norm": ((hd,), "qk"),
        "wo": ((hq * hd, d), ATTN_OUT_GAIN),
        "mlp_norm": ((d,), None),
        "router": ((d, e), 1.0),
        "w_gate": ((e, d, f), 1.0),
        "w_up": ((e, d, f), 1.0),
        "w_down": ((e, f, d), EXPERT_OUT_GAIN),
    }


def init_params(cfg: SdarConfig, rng) -> dict:
    """Seeded weights, stacked ``[L, ...]`` under ``"layers"`` as Mixtral's:
    the gains above; drawn by the device's own generator
    (``nemotron_h.init_params`` says why)."""
    rng = jax.random.wrap_key_data(jax.random.bits(rng, (4,), jnp.uint32),
                                   impl="rbg")
    shapes = _layer_shapes(cfg)
    k = iter(jax.random.split(rng, 2 + len(shapes)))
    nl, d = cfg.num_layers, cfg.hidden_size

    def leaf(shape, gain):
        if gain is None:
            return jnp.ones((nl,) + shape, jnp.float32)
        draw = jax.random.normal(next(k), (nl,) + shape, jnp.float32)
        if gain == "qk":
            return QK_SCORE_STD ** 0.5 * jnp.exp(QK_GAIN_SPREAD * draw)
        return draw * (gain / shape[-2] ** 0.5)

    layers = {name: leaf(*s) for name, s in shapes.items()}
    if QK_SHARE:
        hq, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        of_group = jnp.repeat(layers["wk"].reshape(nl, d, hkv, hd), hq // hkv,
                              axis=2).reshape(nl, d, hq * hd)
        layers["wq"] = (QK_SHARE * of_group
                        + (1.0 - QK_SHARE ** 2) ** 0.5 * layers["wq"])
    return {
        "embed": jax.random.normal(next(k), (cfg.vocab_size, d), jnp.float32),
        "layers": layers,
        "final_norm": jnp.ones((d,), jnp.float32),
        "lm_head": jax.random.normal(next(k), (d, cfg.vocab_size),
                                     jnp.float32) / d ** 0.5,
    }


PARAM_LOGICAL_AXES = {
    "embed": ("vocab", "embed"),
    "layers": {
        "attn_norm": ("layers", "embed"),
        "wq": ("layers", "embed", "heads"),
        "wk": ("layers", "embed", "kv_heads"),
        "wv": ("layers", "embed", "kv_heads"),
        "q_norm": ("layers", None),
        "k_norm": ("layers", None),
        "wo": ("layers", "heads", "embed"),
        "mlp_norm": ("layers", "embed"),
        "router": ("layers", "embed", None),
        "w_gate": ("layers", "experts", "embed", "ffn"),
        "w_up": ("layers", "experts", "embed", "ffn"),
        "w_down": ("layers", "experts", "ffn", "embed"),
    },
    "final_norm": ("embed",),
    "lm_head": ("embed", "vocab"),
}


def forward(cfg: SdarConfig, params, input_ids, masked=None,
            ctx: ShardCtx | None = None):
    """``[B, S]`` token ids -> ``[B, S, V]`` UNSHIFTED logits under the
    block-causal mask, plain XLA (the all-experts einsum, a masked softmax).

    ``masked`` (``[B, S]`` bool): the two-stream replay of a denoise pass. A
    clean stream (``input_ids``) and a noisy one (``input_ids`` with
    ``mask_token_id`` where ``masked``) run together; a noisy row sees the
    CLEAN K and V of the blocks before its own and the NOISY K and V of its
    own block, which is what a denoise pass over that block sees with
    everything before it committed. The noisy stream's logits come back."""
    del ctx
    b, s = input_ids.shape
    blk = cfg.block_length
    pos = jnp.arange(s)
    i, j = pos[:, None] // blk, pos[None, :] // blk
    if masked is None:
        ids, seen = input_ids, j <= i
    else:
        ids = jnp.concatenate(
            [input_ids, jnp.where(masked, cfg.mask_token_id, input_ids)], 1)
        pos = jnp.concatenate([pos, pos])
        seen = jnp.block([[j <= i, jnp.zeros((s, s), bool)],
                          [j < i, j == i]])
    hq, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    n = ids.shape[1]
    bias = jnp.where(seen, 0.0, -1e30)[None, None]
    x = params["embed"][ids]
    for layer in range(cfg.num_layers):
        lp = jax.tree_util.tree_map(lambda a: a[layer].astype(x.dtype),
                                    params["layers"])
        h = rmsnorm(x, lp["attn_norm"], cfg.rms_norm_eps)
        q = rmsnorm((h @ lp["wq"]).reshape(b, n, hq, hd), lp["q_norm"],
                    cfg.rms_norm_eps)
        k = rmsnorm((h @ lp["wk"]).reshape(b, n, hkv, hd), lp["k_norm"],
                    cfg.rms_norm_eps)
        v = (h @ lp["wv"]).reshape(b, n, hkv, hd)
        q, k = apply_rope(q, k, jnp.broadcast_to(pos, (b, n)), cfg.rope_theta)
        o = xla_attention(q, k, v, causal=False, bias=bias)
        x = x + o.reshape(b, n, hq * hd) @ lp["wo"]
        h = rmsnorm(x, lp["mlp_norm"], cfg.rms_norm_eps)
        y = routed_experts_einsum(
            h.reshape(b * n, -1), lp["router"], lp["w_gate"], lp["w_up"],
            lp["w_down"], cfg.top_k)
        x = x + y.reshape(b, n, -1)
    x = rmsnorm(x[:, n - s:], params["final_norm"].astype(x.dtype),
                cfg.rms_norm_eps)
    return x @ params["lm_head"].astype(x.dtype)


# ------------------------------------------------------------------ inference
def init_paged_cache(cfg: SdarConfig, num_blocks: int, block_size: int,
                     dtype=jnp.bfloat16, codec=None, num_slots=None) -> dict:
    """Mixtral's pool, ``{"k", "v"}`` of ``[L, num_blocks, BS, Hkv*D]``: a
    block of rows changes who sees whom, not where a row lives
    (``models/paged.py``, *Blocks of rows*)."""
    del num_slots  # the block a slot denoises is the engine's device state
    from deepspeed_tpu.models.paged import init_paged_pool

    return init_paged_pool(cfg.num_layers, num_blocks, block_size,
                           cfg.num_kv_heads, cfg.head_dim, dtype, codec)


def ragged_forward(cfg: SdarConfig, params, tokens, slots, positions,
                   block_tables, cache, prefill_tiles=None):
    """Flat ragged step: [T] mixed tokens -> ([T, V] unshifted logits,
    cache). The decode region (``prefill_tiles[0]`` rows) is whole blocks of
    ``block_length`` rows a sequence; Mixtral's layer with the head norm and
    the block-causal mask."""
    from deepspeed_tpu.models.paged import scan_layers_paged
    from deepspeed_tpu.ops.quantizer import maybe_dequantize

    x = params["embed"][tokens].astype(cache["k"].dtype)
    layers, stacks = expert_stacks(params["layers"])

    def layer(x, lp, pool, layer_tables):
        x, kc, vc = _ragged_layer(
            cfg, x, lp, pool["k"], pool["v"], positions, slots, layer_tables,
            prefill_tiles=prefill_tiles, stacks=stacks, qk_norm=True,
            block=cfg.block_length)
        return x, {"k": kc, "v": vc}

    x, cache = scan_layers_paged(layer, x, layers, cache, block_tables)
    x = rmsnorm(x, params["final_norm"], cfg.rms_norm_eps)
    logits = x @ maybe_dequantize(params["lm_head"], x.dtype).astype(x.dtype)
    return logits, cache


# ------------------------------------------------------------- arithmetic
def _layer_params(cfg: SdarConfig, experts: float) -> float:
    d, f, hd = cfg.hidden_size, cfg.moe_intermediate_size, cfg.head_dim
    return (d * hd * (2 * cfg.num_heads + 2 * cfg.num_kv_heads) + 2 * hd
            + d * cfg.num_experts + 2 * d + experts * 3 * d * f)


def num_params(cfg: SdarConfig) -> int:
    d = cfg.hidden_size
    return int(2 * cfg.vocab_size * d + d
               + cfg.num_layers * _layer_params(cfg, cfg.num_experts))


def flops_per_token(cfg: SdarConfig, seq_len: int) -> float:
    """Active-parameter training FLOPs (``top_k`` experts a token and layer)
    plus attention over ``seq_len``."""
    active = cfg.vocab_size * cfg.hidden_size + cfg.num_layers * _layer_params(
        cfg, cfg.top_k)
    return (6.0 * active
            + 12.0 * cfg.num_layers * cfg.num_heads * cfg.head_dim
            * seq_len / 2.0)


def build(cfg: SdarConfig, ctx: ShardCtx | None = None) -> ModelSpec:
    ctx = ctx or ShardCtx()
    fwd = partial(forward, cfg, ctx=ctx)

    def loss_fn(params, batch, rng=None):
        del rng  # dropless routing draws nothing
        return causal_lm_loss(fwd(params, batch["input_ids"]),
                              batch["input_ids"], batch.get("labels"))

    return ModelSpec(
        name="sdar",
        config=cfg,
        init_fn=partial(init_params, cfg),
        loss_fn=loss_fn,
        forward_fn=fwd,
        param_logical_axes=PARAM_LOGICAL_AXES,
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
        block_gen=cfg.block_gen,
    )
