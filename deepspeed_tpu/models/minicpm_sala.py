"""MiniCPM-SALA as openbmb/MiniCPM-SALA configures it (``model_type:
minicpm_sala``): every layer is a MIXER, by the published ``mixer_types`` a
block-sparse grouped-query attention layer without positions (``minicpm4``,
InfLLM-V2) or a Lightning-attention layer (``lightning-attn``), and then a
SiLU-gated MLP, under MiniCPM's scalings.

With ``x`` the residual stream, ``L`` the PUBLISHED depth (``depth_layers``,
32, whatever part of the stack is here) and ``r = scale_depth / sqrt(L)``:

- ``x = scale_emb E[id]``.
- a layer: ``x <- x + r Mixer(RMSNorm(x))``, then ``x <- x + r W_d(silu(h
  W_g) * h W_u)`` on ``h = RMSNorm(x)``.
- ``logits = RMSNorm(x) / (hidden / dim_model_base) W_head`` (untied).
- **Lightning layer.** ``q, k, v = h W_q, h W_k, h W_v`` as ``lightning_nh``
  heads of ``lightning_head_dim``; RMSNorm over each head's lanes on q and k
  (``qk_norm``), then RoPE over the whole head (``lightning_use_rope``); a
  head's state ``S_t = lambda S_{t-1} + k_t^T v_t``, ``o_t = (q_t / sqrt(d))
  S_t``, ``lambda = exp(-s)`` a constant a head and layer (``lightning_decay``,
  a ``[layers, heads]`` table of the config: Lightning Attention's own slopes,
  ``2^(-8 (h + 1) / H) (1 - l / (L - 1) + 1e-5)`` at the PUBLISHED layer
  index ``l``); RMSNorm over each head's ``o`` (``use_output_norm``), ``out =
  (o * sigmoid(h W_z)) W_o`` (``use_output_gate``). It runs on the Mamba-2
  path with ``dA`` = the head's decay, ``dtx`` = v, ``B`` = k, ``C`` = q /
  sqrt(d) and a group a head (``G = H``): served, both kernels of
  ``ops/pallas/ssm.py``, ``ssm_decode`` a decode row and ``ssd_chunk`` (the
  chunk form, which at a group a head cuts a head's q, k and v out of the rows
  as the projections leave them) a step's tiles; the plain forward pass,
  ``mamba2.ssd_tiles``.
- **Sparse layer.** ``q`` ``num_heads`` heads, ``k, v`` ``num_kv_heads`` heads
  (a group of ``rep`` query heads a K/V head), RMSNorm a head on q and k, NO
  positions; the heads' output times ``sigmoid(h W_z)`` before ``W_o``. A
  query at position ``t`` of group ``g``:

  - ``t + 1 <= dense_len``: causal softmax attention over every key.
  - else: compressed keys ``K_g[j] = mean_{s in [S j, S j + K)} k_g[s]``
    (``kernel_size`` ``K``, ``kernel_stride`` ``S``), visible once ``S j + K -
    1 <= t``; ``p_h[t, .] = softmax_j(q_h[t] . K_g[j] / sqrt(d))`` over the
    visible ``j`` (exact, float32); ``P_g[t, j] = sum_{h in g} p_h[t, j]``; a
    block ``b`` (tokens ``[B b, B b + B)``, ``block_size`` ``B``) scores the
    maximum of ``P_g[t, j]`` over the visible kernels that overlap it (-inf
    with none); the first ``init_blocks`` blocks and the ``window_size / B``
    blocks ending at the query's own score +inf; the query keeps the ``topk``
    best blocks up to its own, the lowest block first among equals
    (``deepseek_v32.select_mask``, the repo's exact top-k), and attends by
    causal softmax over the keys inside them.

  The switch at ``dense_len`` is by POSITION (the family's published code
  switches by a call's length, which with a cache would make a row depend on
  how its prompt was cut into steps).

**The stack.** ``mixer_types`` is cut into RUNS of one kind and the parameters
are kept a run a stack (``params["runs"]``), each scanned where it lies
(``paged.scan_runs_paged``, ``granite_hybrid``'s form): the benchmark's eight
layers ``S L L L L L L S`` are three runs and three layer bodies.

**Serving** (``models/paged.py``). A sparse layer keeps THREE block leaves
behind its table: ``"k"`` / ``"v"`` ``[L_s, NB, BS, Hkv*D]`` and the compressed
keys ``"ck"`` ``[L_s, NB, BS / S, Hkv*D]``, key ``j`` of a sequence in row ``j
% (BS / S)`` of its block ``j // (BS / S)``, written in the step whose rows
complete kernel ``j`` (every ``S``-th token past the first ``K - 1``; the mean
is over the pool's rows, the step's own among them). A Lightning layer's state
is a slot leaf ``"ssm"`` ``[L_l, S, d, H*d]`` float32 (``mamba2``'s layout). A
page (``BS``) may hold several selection blocks and several prefill tiles: a
layer sees the pool in blocks of the size it needs (``paged.sub_blocks``, a
bitcast). A step scatters K, V, then the compressed keys its rows complete;
scores every row against its sequence's compressed keys (XLA: a gather of the
row's table of them), selects, and attends: a decode row over the blocks it
kept and nothing else (``ops/pallas/bsa_attention.bsa_decode``: the selection
as a block table a K/V head), a prefill tile over every block up to its last
position under the selection as a bias (``bsa_prefill``). Off the chip the
same steps run as plain XLA gathers (``attend_xla``).

The seeded draw (``init_params``) is under ``assumed`` in the benchmark's
configuration: a random model's attention is flat and its layers add little,
so the check would see no selection and no state (ROADMAP B9).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from itertools import accumulate, groupby

import jax
import jax.numpy as jnp
from jax import lax

from deepspeed_tpu.models import mamba2
from deepspeed_tpu.models.api import (
    BlockSelection,
    ModelSpec,
    ShardCtx,
    causal_lm_loss,
)
from deepspeed_tpu.models.deepseek_v32 import select_mask
from deepspeed_tpu.models.llama import rmsnorm
from deepspeed_tpu.ops.attention import apply_rope

KINDS = ("minicpm4", "lightning-attn")
PUBLISHED_SPARSE = (0, 9, 16, 17, 22, 29, 30, 31)
DECODE_BUCKET_MIN = 16
_NEG_INF = -1e30
# The seeded draw (module doc; PERF.md section 6, PR 60 has the readings, all
# on the chip at the cell's sizes). A sparse layer's q and k gains are 1, so
# that a score of two normed rows is one wide: at 3 wide a few keys carry a
# row's softmax, the bfloat16 rounding of the stream moves them, and the plain
# bfloat16 reference agreed with the float32 one on 0.47 of its greedy tokens.
QK_SCORE_STD = 1.0
# the gates' pre-activation on a normed row (``solar_open2``'s draw of W_g)
GATE_PREACT_STD = 1.0
# A mixer's W_o against 0.02. A softmax one wide over 4,096 to 20,000 keys is
# a mean of ~1,500 to 7,000 values, a fortieth to an eightieth of one: at 0.02
# the sparse branch is a thousandth of the stream and no fault in the
# selection moves a logit; at 40 x it weighed what a Lightning layer's does
# and ONE block of 64 traded at the selection's threshold (bfloat16 against
# float32 scores) moved a sum of 64 blocks by a sixth: agreement 0.66-0.76.
# At 8 x the selection left out reads 0.12 and the served path 0.87. A
# Lightning layer's output passes a norm: at 2 x the state lost at a prompt's
# last tile reads 0.59, at 1 x 0.68 against a served 0.76.
SPARSE_OUT_GAIN = 8.0
LIGHTNING_OUT_GAIN = 2.0


def lightning_slopes(heads: int, layer: int, depth: int):
    """Lightning Attention's decay rates of published layer ``layer`` of
    ``depth``: ``s_h = 2^(-8 (h + 1) / H) (1 - l / (L - 1) + 1e-5)``."""
    return tuple(2.0 ** (-8.0 * (h + 1) / heads)
                 * (1.0 - layer / (depth - 1) + 1e-5) for h in range(heads))


@dataclass(frozen=True)
class MiniCPMSalaConfig:
    vocab_size: int = 73448
    hidden_size: int = 4096
    num_layers: int = 32
    mixer_types: tuple = tuple(
        "minicpm4" if i in PUBLISHED_SPARSE else "lightning-attn"
        for i in range(32))
    # the published depth: r = scale_depth / sqrt(depth_layers), and the
    # decay table's L, whatever part of the stack is here
    depth_layers: int = 32
    # the published index of this stack's first layer
    first_layer: int = 0
    num_heads: int = 32
    num_kv_heads: int = 2
    head_dim: int = 128
    lightning_nh: int = 32
    lightning_nkv: int = 32
    lightning_head_dim: int = 128
    intermediate_size: int = 16384
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    dim_model_base: int = 256
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    # ``[num_layers, lightning_nh]`` decay rates s (lambda = exp(-s)), a row a
    # layer of THIS stack (a sparse layer's row is unread); None: the slopes
    # of ``lightning_slopes`` at the published indices
    lightning_decay: tuple | None = None
    # MiniCPM4's sparse_config
    kernel_size: int = 32
    kernel_stride: int = 16
    block_size: int = 64
    init_blocks: int = 1
    window_size: int = 2048
    topk: int = 64
    dense_len: int = 8192
    chunk_size: int = 128      # the plain forward pass's Lightning chunk
    max_seq_len: int = 524288

    def __post_init__(self):
        object.__setattr__(self, "mixer_types", tuple(self.mixer_types))
        if len(self.mixer_types) != self.num_layers \
                or set(self.mixer_types) - set(KINDS):
            raise ValueError(
                "minicpm_sala: mixer_types must name each of the "
                f"{self.num_layers} layers as one of {KINDS}")
        if self.num_heads % self.num_kv_heads \
                or self.lightning_nkv != self.lightning_nh:
            raise ValueError(
                "minicpm_sala: num_kv_heads must divide num_heads and a "
                "Lightning key head be a query head's (lightning_nkv == "
                "lightning_nh, as published)")
        b, k, s = self.block_size, self.kernel_size, self.kernel_stride
        if b % s or k % s or self.window_size % b or self.dense_len % b \
                or self.topk < self.init_blocks + self.window_size // b:
            raise ValueError(
                "minicpm_sala: kernel_stride must divide block_size and "
                "kernel_size, block_size window_size and dense_len, and topk "
                "hold the forced blocks")
        if self.lightning_decay is None:
            object.__setattr__(self, "lightning_decay", tuple(
                lightning_slopes(self.lightning_nh, self.first_layer + i,
                                 self.depth_layers)
                for i in range(self.num_layers)))
        else:
            object.__setattr__(self, "lightning_decay", tuple(
                tuple(float(s) for s in row) for row in self.lightning_decay))
        if len(self.lightning_decay) != self.num_layers or any(
                len(row) != self.lightning_nh for row in self.lightning_decay):
            raise ValueError("minicpm_sala: lightning_decay is [num_layers, "
                             "lightning_nh]")

    @property
    def residual_scale(self) -> float:
        return self.scale_depth / self.depth_layers ** 0.5

    @property
    def logits_divisor(self) -> float:
        return self.hidden_size / self.dim_model_base

    @property
    def rep(self) -> int:
        return self.num_heads // self.num_kv_heads

    @property
    def local_blocks(self) -> int:
        return self.window_size // self.block_size

    @property
    def kept_keys(self) -> int:
        """Keys a selecting query keeps at most: ``ModelSpec.index_topk``."""
        return self.topk * self.block_size

    @property
    def list_blocks(self) -> int:
        """Blocks a decode row's list can hold: every one under the dense
        length, ``topk`` past it."""
        return max(self.topk, self.dense_len // self.block_size)

    def layers_of(self, kind: str) -> int:
        return self.mixer_types.count(kind)

    @property
    def runs(self) -> list:
        """``[(kind, layers)]``: ``mixer_types`` as runs of one kind."""
        return [(kind, len(list(g))) for kind, g in groupby(self.mixer_types)]

    @staticmethod
    def tiny(vocab_size: int = 256,
             mixer_types=("minicpm4", "lightning-attn", "lightning-attn",
                          "minicpm4"), **over) -> "MiniCPMSalaConfig":
        """4 query heads on 2 K/V heads of 16, 4 Lightning heads of 16;
        kernels of 4 every 2, blocks of 8, one initial and two local blocks
        among 5 kept, dense up to 24 keys."""
        return MiniCPMSalaConfig(**{**dict(
            vocab_size=vocab_size, hidden_size=64, num_layers=len(mixer_types),
            mixer_types=mixer_types, depth_layers=8, first_layer=2,
            num_heads=4, num_kv_heads=2, head_dim=16, lightning_nh=4,
            lightning_nkv=4, lightning_head_dim=16, intermediate_size=96,
            kernel_size=4, kernel_stride=2, block_size=8, init_blocks=1,
            window_size=16, topk=5, dense_len=24, chunk_size=8,
            max_seq_len=256), **over})


# ------------------------------------------------------------------ weights
def _mixer_shapes(cfg: MiniCPMSalaConfig, kind: str) -> dict:
    d = cfg.hidden_size
    if kind == "minicpm4":
        hq, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        norms = {"q_norm": ((hd,), "qk"), "k_norm": ((hd,), "qk")}
    else:
        hq = hkv = cfg.lightning_nh
        hd = cfg.lightning_head_dim
        norms = {"q_norm": ((hd,), "ones"), "k_norm": ((hd,), "ones"),
                 "o_norm": ((hd,), "ones")}
    return {"wq": ((d, hq * hd), 0.02), "wk": ((d, hkv * hd), 0.02),
            "wv": ((d, hkv * hd), 0.02), "w_z": ((d, hq * hd), "gate"),
            "wo": ((hq * hd, d), kind + "_out"), **norms}


def _layer_shapes(cfg: MiniCPMSalaConfig, kind: str) -> dict:
    d, f = cfg.hidden_size, cfg.intermediate_size
    return {"norm": ((d,), "ones"), "mix": _mixer_shapes(cfg, kind),
            "ffn_norm": ((d,), "ones"),
            "ffn": {"w_gate": ((d, f), 0.02), "w_up": ((d, f), 0.02),
                    "w_down": ((f, d), 0.02)}}


def _is_shape(s) -> bool:
    return isinstance(s, tuple)


def init_params(cfg: MiniCPMSalaConfig, rng) -> dict:
    """Seeded weights, a run of layers a stack (module doc), from the
    device's own generator (``nemotron_h.init_params`` says why). std 0.02
    but: a sparse layer's q and k gains ``sqrt(QK_SCORE_STD)`` each; ``W_z``
    at ``GATE_PREACT_STD / sqrt(hidden)``, so that a gate is no constant 0.5;
    the mixers' ``W_o`` ``SPARSE_OUT_GAIN`` / ``LIGHTNING_OUT_GAIN`` times
    0.02; the head ``hidden / dim_model_base`` times 0.02, so that the
    published division leaves the logits as wide as the other families'."""
    rng = jax.random.wrap_key_data(jax.random.bits(rng, (4,), jnp.uint32),
                                   impl="rbg")
    draws = 2 + sum(init not in ("ones", "qk") for kind, _ in cfg.runs
                    for _, init in jax.tree_util.tree_leaves(
                        _layer_shapes(cfg, kind), is_leaf=_is_shape))
    k = iter(jax.random.split(rng, draws))
    stds = {"gate": GATE_PREACT_STD * cfg.hidden_size ** -0.5,
            "minicpm4_out": 0.02 * SPARSE_OUT_GAIN,
            "lightning-attn_out": 0.02 * LIGHTNING_OUT_GAIN}

    def leaf(stack, shape, init):
        shape = stack + shape
        if init in ("ones", "qk"):
            return jnp.full(shape, QK_SCORE_STD ** 0.5 if init == "qk"
                            else 1.0, jnp.float32)
        return jax.random.normal(next(k), shape, jnp.float32) \
            * stds.get(init, init)

    return {
        "embed": leaf((), (cfg.vocab_size, cfg.hidden_size), 0.02),
        "runs": [jax.tree_util.tree_map(
            lambda s, n=n: leaf((n,), *s), _layer_shapes(cfg, kind),
            is_leaf=_is_shape) for kind, n in cfg.runs],
        "final_norm": jnp.ones((cfg.hidden_size,), jnp.float32),
        "lm_head": leaf((), (cfg.hidden_size, cfg.vocab_size),
                        0.02 * cfg.logits_divisor),
    }


_AXES = {"wq": ("embed", "heads"), "wk": ("embed", "kv_heads"),
         "wv": ("embed", "kv_heads"), "w_z": ("embed", "heads"),
         "wo": ("heads", "embed"), "w_gate": ("embed", "ffn"),
         "w_up": ("embed", "ffn"), "w_down": ("ffn", "embed"),
         "norm": ("embed",), "ffn_norm": ("embed",)}


def param_logical_axes(cfg: MiniCPMSalaConfig) -> dict:
    def layer(kind):
        return jax.tree_util.tree_map_with_path(
            lambda path, s: ("layers",) + _AXES.get(
                path[-1].key, (None,) * len(s[0])),
            _layer_shapes(cfg, kind), is_leaf=_is_shape)

    return {"embed": ("vocab", "embed"),
            "runs": [layer(kind) for kind, _ in cfg.runs],
            "final_norm": ("embed",), "lm_head": ("embed", "vocab")}


def _run_decays(cfg: MiniCPMSalaConfig) -> list:
    """``[n, H]`` decay rates of each run's layers (a run a stack)."""
    table = jnp.asarray(cfg.lightning_decay, jnp.float32)
    firsts = accumulate((n for _, n in cfg.runs), initial=0)
    return [table[first:first + n] for (_, n), first in zip(cfg.runs, firsts)]


# ------------------------------------------------------------------ pieces
def _mlp(cfg: MiniCPMSalaConfig, x, lp):
    h = rmsnorm(x, lp["ffn_norm"], cfg.rms_norm_eps)
    f = lp["ffn"]
    y = (jax.nn.silu(h @ f["w_gate"]) * (h @ f["w_up"])) @ f["w_down"]
    return x + y.astype(x.dtype) * cfg.residual_scale


def _head(cfg: MiniCPMSalaConfig, params, x):
    x = rmsnorm(x, params["final_norm"], cfg.rms_norm_eps) / cfg.logits_divisor
    return x @ params["lm_head"].astype(x.dtype)


# --------------------------------------------------------------- selection
def compressed_visible(cfg: MiniCPMSalaConfig, positions, n_cmp: int):
    """``[..., n_cmp]`` bool: compressed key ``j`` is whole by ``positions``."""
    j = jnp.arange(n_cmp, dtype=jnp.int32)
    return cfg.kernel_stride * j + cfg.kernel_size - 1 <= positions[..., None]


def group_scores(cfg: MiniCPMSalaConfig, q, ck, positions):
    """Stage one. ``q`` [I, R, Hkv, rep, D] (``R`` rows of each of ``I``
    sequences), ``ck`` [I, J, Hkv, D] their compressed keys, ``positions``
    [I, R] -> ``P`` [I, R, Hkv, J] float32, the group's heads' softmaxes over
    the visible keys summed, -inf on a key that is not visible."""
    f32 = jnp.float32
    s = jnp.einsum("irgqd,ijgd->irgqj", q.astype(f32), ck.astype(f32),
                   precision=lax.Precision.HIGHEST) * q.shape[-1] ** -0.5
    vis = compressed_visible(cfg, positions, ck.shape[1])[:, :, None, None]
    s = jnp.where(vis, s, -jnp.inf)
    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.where(vis, jnp.exp(s - jnp.where(jnp.isfinite(m), m, 0.0)), 0.0)
    p = e / jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)
    return jnp.where(vis[:, :, :, 0], jnp.sum(p, axis=3), -jnp.inf)


def kept_blocks(cfg: MiniCPMSalaConfig, p, positions, n_blocks: int):
    """Stage two. ``p`` [..., Hkv, J] (``group_scores``), ``positions`` [...]
    -> ``keep`` [..., Hkv, n_blocks] bool: the blocks each query's groups
    keep; every block up to its own for a query under the dense length."""
    bsz, ksz, stride = cfg.block_size, cfg.kernel_size, cfg.kernel_stride
    n_cmp = p.shape[-1]
    b = jnp.arange(n_blocks, dtype=jnp.int32)
    # the kernels that overlap block b: lo .. hi
    lo = (bsz * b - ksz) // stride + 1
    hi = (bsz * (b + 1) - 1) // stride
    span = (bsz + ksz) // stride - 1
    idx = lo[:, None] + jnp.arange(span, dtype=jnp.int32)
    ok = (idx >= 0) & (idx <= hi[:, None]) & (idx < n_cmp)
    score = jnp.max(jnp.where(ok, p[..., jnp.clip(idx, 0, n_cmp - 1)],
                              -jnp.inf), axis=-1)       # [..., Hkv, NB]
    own = (positions // bsz)[..., None, None]
    forced = (b < cfg.init_blocks) | ((b <= own) & (b > own - cfg.local_blocks))
    score = jnp.where(forced, jnp.inf, score)
    lead = score.shape[:-1]
    rows_own = jnp.broadcast_to(own, lead + (1,)).reshape(-1)
    mask = select_mask(score.reshape(-1, n_blocks), rows_own,
                       cfg.topk).reshape(lead + (n_blocks,))
    dense = (positions + 1 <= cfg.dense_len)[..., None, None]
    return jnp.where(dense, b <= own, mask)


def compress_keys(cfg: MiniCPMSalaConfig, k):
    """``k`` [S, Hkv, D] a whole sequence's keys -> [S / stride, Hkv, D]
    float32, key ``j`` the mean of rows ``stride j .. stride j + kernel - 1``
    (a kernel that runs past the sequence is never visible)."""
    s = k.shape[0]
    n = -(-s // cfg.kernel_stride)
    pad = n * cfg.kernel_stride + cfg.kernel_size - s
    kf = jnp.pad(k.astype(jnp.float32), ((0, pad), (0, 0), (0, 0)))
    idx = (jnp.arange(n)[:, None] * cfg.kernel_stride
           + jnp.arange(cfg.kernel_size))
    return jnp.mean(kf[idx], axis=1)


# -------------------------------------------------------------------- forward
def _sparse_sequence(cfg: MiniCPMSalaConfig, lp, h):
    """The sparse mixer over one whole sequence ``h`` [S, D]: dense ``[S,
    S]`` scores under the selection's mask."""
    s = h.shape[0]
    f32 = jnp.float32
    hq, hkv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = rmsnorm((h @ lp["wq"]).reshape(s, hkv, cfg.rep, d), lp["q_norm"],
                   cfg.rms_norm_eps)
    k = rmsnorm((h @ lp["wk"]).reshape(s, hkv, d), lp["k_norm"],
                   cfg.rms_norm_eps)
    v = (h @ lp["wv"]).reshape(s, hkv, d)
    pos = jnp.arange(s, dtype=jnp.int32)
    n_blocks = -(-s // cfg.block_size)
    ck = compress_keys(cfg, k).astype(h.dtype)
    p = group_scores(cfg, q[None], ck[None], pos[None])[0]
    keep = kept_blocks(cfg, p, pos, n_blocks)               # [S, Hkv, NB]
    seen = jnp.repeat(keep, cfg.block_size, axis=-1)[..., :s] \
        & (pos[None, :] <= pos[:, None])[:, None]
    scores = jnp.einsum("tgqd,sgd->tgqs", q.astype(f32), k.astype(f32)
                        ) * d ** -0.5
    w = jax.nn.softmax(jnp.where(seen[:, :, None], scores, _NEG_INF), axis=-1)
    o = jnp.einsum("tgqs,sgd->tgqd", w, v.astype(f32)).astype(h.dtype)
    gate = jax.nn.sigmoid(h @ lp["w_z"])
    return (o.reshape(s, hq * d) * gate) @ lp["wo"]


def _lightning_qkv(cfg: MiniCPMSalaConfig, h, lp, positions):
    """``h`` [T, D] (normed) -> q (scaled), k [T, H, d] normed and rotated,
    v [T, H, d]."""
    from deepspeed_tpu.models.paged import rows_to_heads

    nh, d = cfg.lightning_nh, cfg.lightning_head_dim
    q = rmsnorm(rows_to_heads(h, lp["wq"], nh), lp["q_norm"],
                   cfg.rms_norm_eps)
    k = rmsnorm(rows_to_heads(h, lp["wk"], nh), lp["k_norm"],
                   cfg.rms_norm_eps)
    q, k = apply_rope(q[None], k[None], positions[None], cfg.rope_theta)
    return (q[0] * jnp.asarray(d ** -0.5, h.dtype), k[0],
            rows_to_heads(h, lp["wv"], nh))


def _lightning_out(cfg: MiniCPMSalaConfig, y, h, lp):
    """``y`` [T, H x d] float32 (the state's readout) -> the mixer's output:
    the head norm, the gate, ``W_o``."""
    t = y.shape[0]
    o = rmsnorm(y.reshape(t, cfg.lightning_nh, -1), lp["o_norm"],
                   cfg.rms_norm_eps).reshape(t, -1).astype(h.dtype)
    return (o * jax.nn.sigmoid(h @ lp["w_z"])) @ lp["wo"]


def _lightning_sequence(cfg: MiniCPMSalaConfig, lp, decay, h):
    """The Lightning mixer over one whole sequence ``h`` [S, D] from an empty
    state: a scan over chunks of the form the serving tiles run."""
    s, r = h.shape[0], cfg.chunk_size
    nh, d = cfg.lightning_nh, cfg.lightning_head_dim
    q, k, v = _lightning_qkv(cfg, h, lp, jnp.arange(s, dtype=jnp.int32))
    pad = -s % r
    live = jnp.pad(jnp.ones((s, nh), jnp.float32), ((0, pad), (0, 0)))

    def chunks(t):
        return jnp.pad(t, ((0, pad),) + ((0, 0),) * (t.ndim - 1)).reshape(
            (-1, r) + t.shape[1:])

    def chunk(state, xs):
        v_c, dt_c, k_c, q_c = (t[None] for t in xs)
        y, state = mamba2.ssd_tiles(cfg, v_c, dt_c, -decay, k_c, q_c,
                                    state[None], jnp.zeros((1,), bool))
        return state[0], y[0]

    _, y = lax.scan(chunk, jnp.zeros((d, nh * d), jnp.float32),
                    (chunks(v), live.reshape(-1, r, nh), chunks(k), chunks(q)))
    return _lightning_out(cfg, y.reshape(-1, nh * d)[:s], h, lp)


def forward(cfg: MiniCPMSalaConfig, params, input_ids,
            ctx: ShardCtx | None = None):
    """``[B, S]`` token ids -> ``[B, S, V]`` logits: the plain forward pass
    (no cache), the layers in ``mixer_types``' order."""
    ctx = ctx or ShardCtx()
    x = ctx.embed_lookup(params["embed"], input_ids, "batch", "seq", "embed_act")
    x = x * cfg.scale_emb
    for (kind, n), stack, decays in zip(cfg.runs, params["runs"],
                                        _run_decays(cfg)):
        for i in range(n):
            lp = ctx.layer_weights(
                jax.tree_util.tree_map(lambda a: a[i], stack), x.dtype)  # noqa: B023
            h = rmsnorm(x, lp["norm"], cfg.rms_norm_eps)
            if kind == "minicpm4":
                o = jax.vmap(partial(_sparse_sequence, cfg, lp["mix"]))(h)
            else:
                o = jax.vmap(partial(_lightning_sequence, cfg, lp["mix"],
                                     decays[i]))(h)
            x = x + o.astype(x.dtype) * cfg.residual_scale
            x = ctx.constrain(_mlp(cfg, x, lp), "batch", "seq", "embed_act")
    return ctx.constrain(_head(cfg, params, x), "batch", "seq", "vocab_act")


# ------------------------------------------------------------------ inference
def init_paged_cache(cfg: MiniCPMSalaConfig, num_blocks: int, block_size: int,
                     dtype=jnp.bfloat16, codec=None,
                     num_slots: int | None = None) -> dict:
    """The cache of the ragged engine (module doc, *Serving*): the sparse
    layers' ``{"k", "v"}`` ``[L_s, NB, BS, Hkv*D]`` and compressed keys
    ``"ck"`` ``[L_s, NB, BS / stride, Hkv*D]``, the Lightning layers' state
    under ``"slots"``: ``"ssm"`` ``[L_l, S, d, H*d]`` float32."""
    from deepspeed_tpu.models.paged import SLOTS, init_paged_pool

    if codec is not None:
        raise NotImplementedError(
            "minicpm_sala: a quantized pool is not implemented beside slot "
            "state (the engine refuses it too)")
    if num_slots is None:
        raise ValueError("minicpm_sala: the cache needs the engine's slot "
                         "count (num_slots = max_seqs + 1) for its state")
    if block_size % cfg.block_size:
        raise ValueError(
            f"minicpm_sala: a page of {block_size} tokens is no whole number "
            f"of the selection's {cfg.block_size}-token blocks")
    n_sparse = cfg.layers_of("minicpm4")
    cache = init_paged_pool(n_sparse, num_blocks, block_size,
                            cfg.num_kv_heads, cfg.head_dim, dtype)
    cache["ck"] = jnp.zeros(
        (n_sparse, num_blocks, block_size // cfg.kernel_stride,
         cfg.num_kv_heads * cfg.head_dim), dtype)
    cache[SLOTS] = {"ssm": jnp.zeros(
        (cfg.layers_of("lightning-attn"), num_slots, cfg.lightning_head_dim,
         cfg.lightning_nh * cfg.lightning_head_dim), jnp.float32)}
    return cache


def write_compressed(cfg: MiniCPMSalaConfig, ck_pool, k_pool, kk, slots,
                     positions, tables, prefill_tiles):
    """Write the compressed keys the step's rows complete (``kk`` [T, Hkv*D],
    already in ``k_pool``): a row at ``t`` completes kernel ``(t - K + 1) /
    S`` where that is a whole number >= 0. A decode row's mean is over the
    pool's last ``K`` rows of its sequence; a tile's over the ``K - 1`` rows
    before it from the pool and its own. A row that completes none writes the
    scratch block."""
    f32 = jnp.float32
    ksz, stride = cfg.kernel_size, cfg.kernel_stride
    bs, per = k_pool.shape[1], ck_pool.shape[1]
    flat = k_pool.reshape((-1,) + k_pool.shape[2:])

    def rows_at(slot, pos):
        """The pool's rows of ``slot`` [n] at ``pos`` [n, m] (clipped at 0)."""
        pos = jnp.maximum(pos, 0)
        blk = jnp.take_along_axis(tables[slot], pos // bs, axis=1)
        return flat[blk * bs + pos % bs]

    def scatter(pool, slot, j, ok, vals):
        blk = jnp.where(ok, jnp.take_along_axis(
            tables[slot], jnp.clip(j // per, 0, tables.shape[1] - 1)[:, None],
            axis=1)[:, 0], 0)
        return pool.at[blk, jnp.where(ok, j % per, 0)].set(
            vals.astype(pool.dtype))

    t = kk.shape[0]
    n_dec = t if prefill_tiles is None else prefill_tiles[0]
    if n_dec:
        pos = positions[:n_dec]
        j = (pos - ksz + 1) // stride
        ok = (pos >= ksz - 1) & ((pos - ksz + 1) % stride == 0)
        win = rows_at(slots[:n_dec], pos[:, None] - ksz + 1
                      + jnp.arange(ksz, dtype=jnp.int32))
        ck_pool = scatter(ck_pool, slots[:n_dec], j, ok,
                          jnp.mean(win.astype(f32), axis=1))
    if t > n_dec:
        _, ts, tp, tv, r = prefill_tiles
        n_i, n_c = ts.shape[0], -(-r // stride)
        prev = rows_at(ts, tp[:, None] - ksz + 1
                       + jnp.arange(ksz - 1, dtype=jnp.int32))
        ext = jnp.concatenate([prev, kk[n_dec:].reshape(n_i, r, -1)], axis=1)
        # candidate c of a tile: the c-th row at or past pos0 that ends a
        # kernel; its window starts ``off`` rows into ``ext``
        off = ((ksz - 1 - tp) % stride)[:, None] \
            + stride * jnp.arange(n_c, dtype=jnp.int32)      # [n_i, n_c]
        end = tp[:, None] + off
        ok = (off < tv[:, None]) & (end >= ksz - 1)
        e = jnp.arange(ext.shape[1], dtype=jnp.int32)
        mean = ((e >= off[..., None]) & (e < off[..., None] + ksz)
                ).astype(f32) / ksz                          # [n_i, n_c, E]
        vals = jnp.einsum("ice,iel->icl", mean, ext.astype(f32),
                          precision=lax.Precision.HIGHEST)
        ck_pool = scatter(ck_pool, jnp.repeat(ts, n_c),
                          ((end - ksz + 1) // stride).reshape(-1),
                          ok.reshape(-1), vals.reshape(n_i * n_c, -1))
    return ck_pool


def attend_xla(q, kc, vc, keep, slots, positions, tables, block: int):
    """Attention over the kept blocks as plain XLA: every row gathers its
    sequence's whole table of K and V. ``q`` [T, Hkv, rep, D], ``keep`` [T,
    Hkv, NB] -> [T, Hkv, rep, D]. The form the CPU runs, and the kernels'
    yardstick."""
    f32 = jnp.float32
    t, hkv, _, d = q.shape
    ctx_k, ctx_v = (pool[tables[slots]].reshape(t, -1, hkv, d).astype(f32)
                    for pool in (kc, vc))
    keys = ctx_k.shape[1]
    seen = jnp.repeat(keep, block, axis=-1)[..., :keys] \
        & (jnp.arange(keys)[None, :] <= positions[:, None])[:, None]
    s = jnp.einsum("tgqd,tsgd->tgqs", q.astype(f32), ctx_k) * d ** -0.5
    w = jax.nn.softmax(jnp.where(seen[:, :, None], s, _NEG_INF), axis=-1)
    return jnp.einsum("tgqs,tsgd->tgqd", w, ctx_v).astype(q.dtype)


def kept_lists(cfg: MiniCPMSalaConfig, keep, slots, positions, tables):
    """A decode row's selection as a block table a K/V head: ``keep`` [T,
    Hkv, NB] -> ``(ids [T, Hkv, W], n_keys [T, Hkv])``, the kept blocks in
    position order as blocks of ``tables`` (the pool seen in the selection's
    blocks) and the keys the row sees in them. The j-th kept block is the
    number of blocks with fewer than ``j + 1`` kept up to them
    (``deepseek_v32._gather_kept``'s compare-and-count)."""
    width = min(cfg.list_blocks, keep.shape[-1])
    upto = jnp.cumsum(keep, axis=-1, dtype=jnp.int32)          # [T, Hkv, NB]
    n_kept = upto[..., -1]
    j = jnp.arange(width, dtype=jnp.int32)
    idx = jnp.sum(upto[..., None, :] <= j[:, None], axis=-1, dtype=jnp.int32)
    idx = jnp.where(j < n_kept[..., None], idx, 0)
    ids = jnp.take_along_axis(tables[slots][:, None], idx, axis=-1)
    n_keys = (n_kept - 1) * cfg.block_size \
        + (positions % cfg.block_size)[:, None] + 1
    return ids, jnp.maximum(n_keys, 1)


def sparse_attention_ragged(cfg: MiniCPMSalaConfig, h, lp, pool, layer_tables,
                            slots, positions, prefill_tiles,
                            impl: str = "auto"):
    """A sparse layer over the normed rows ``h`` [T, D] of a flat ragged
    batch -> ``(out [T, D], pool)`` (module doc, *Serving*)."""
    from deepspeed_tpu.models.paged import (
        rows_to_heads,
        sub_blocks,
        write_kv_paged,
    )
    from deepspeed_tpu.ops.attention import _on_tpu
    from deepspeed_tpu.ops.pallas import bsa_attention as bsa

    if impl == "auto":
        impl = "pallas" if _on_tpu() else "xla"
    t = h.shape[0]
    hkv, rep, d = cfg.num_kv_heads, cfg.rep, cfg.head_dim
    bsz = cfg.block_size
    q = rmsnorm(rows_to_heads(h, lp["wq"], cfg.num_heads), lp["q_norm"],
                   cfg.rms_norm_eps).reshape(t, hkv, rep, d)
    kk = rmsnorm(rows_to_heads(h, lp["wk"], hkv), lp["k_norm"],
                    cfg.rms_norm_eps)
    vv = rows_to_heads(h, lp["wv"], hkv)
    kc, vc = write_kv_paged(pool["k"], pool["v"], kk, vv, slots, positions,
                            layer_tables, prefill_tiles)
    ck = write_compressed(cfg, pool["ck"], kc, kk.reshape(t, -1), slots,
                          positions, layer_tables, prefill_tiles)
    n_dec = t if prefill_tiles is None else prefill_tiles[0]
    n_blocks = layer_tables.shape[1] * kc.shape[1] // bsz
    scale = d ** -0.5

    def keys_of(seqs):   # [I, J, Hkv, D]: the sequences' compressed keys
        return ck[layer_tables[seqs]].reshape(seqs.shape[0], -1, hkv, d)

    parts = []
    if n_dec:
        sl, pos = slots[:n_dec], positions[:n_dec]
        p = group_scores(cfg, q[:n_dec, None], keys_of(sl), pos[:, None])[:, 0]
        keep = kept_blocks(cfg, p, pos, n_blocks)
        if impl == "pallas":
            k_sel, sel_tables = sub_blocks(kc, layer_tables, bsz)
            v_sel, _ = sub_blocks(vc, layer_tables, bsz)
            ids, n_keys = kept_lists(cfg, keep, sl, pos, sel_tables)
            parts.append(bsa.bsa_decode_attention(
                q[:n_dec], k_sel, v_sel, ids, n_keys, scale))
        else:
            parts.append(attend_xla(q[:n_dec], kc, vc, keep, sl, pos,
                                    layer_tables, bsz))
    if t > n_dec:
        _, ts, tp, tv, r = prefill_tiles
        n_i = ts.shape[0]
        pos = tp[:, None] + jnp.arange(r, dtype=jnp.int32)
        p = group_scores(cfg, q[n_dec:].reshape(n_i, r, hkv, rep, d),
                         keys_of(ts), pos)
        keep = kept_blocks(cfg, p, pos, n_blocks).reshape(n_i * r, hkv, -1)
        if impl == "pallas":
            o = bsa.bsa_prefill_attention(
                q[n_dec:].reshape(n_i * r, hkv * rep, d), kc, vc, keep, ts,
                tp, tv, layer_tables, r, scale)
            parts.append(o.reshape(n_i * r, hkv, rep, d))
        else:
            # rows past a tile's valid ones read their own tile's slot, masked
            parts.append(attend_xla(
                q[n_dec:], kc, vc, keep, jnp.repeat(ts, r), pos.reshape(-1),
                layer_tables, bsz))
    o = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
    o = o.reshape(t, -1).astype(h.dtype) * jax.nn.sigmoid(h @ lp["w_z"])
    return o @ lp["wo"], {**pool, "k": kc, "v": vc, "ck": ck}


def lightning_ragged(cfg: MiniCPMSalaConfig, h, lp, decay, ssm, slot0, scratch,
                     slots, positions, prefill_tiles):
    """A Lightning layer over the normed rows ``h`` [T, D] of a flat ragged
    batch -> ``(out [T, D], ssm)``: ``ssm`` the state leaf, layers and slots
    merged, this layer's slot ``s`` row ``slot0 + s``; decode rows through
    ``ssm_decode``, tiles through ``ssd_chunk`` (both ``ops/pallas/ssm.py``),
    ``mamba2.ragged``'s rules for a step's rows (``dt`` = 1 on a real row, 0
    on padding)."""
    from deepspeed_tpu.ops.pallas.ssm import ssd_chunk, ssm_decode

    f32 = jnp.float32
    t = h.shape[0]
    nh, d = cfg.lightning_nh, cfg.lightning_head_dim
    q, k, v = _lightning_qkv(cfg, h, lp, positions)
    # both kernels take a row's values flat, as the projection left them
    v = v.reshape(t, nh * d)
    n_dec = t if prefill_tiles is None else prefill_tiles[0]
    ys = []
    if n_dec:
        real = slots[:n_dec] != scratch
        fresh = real & (positions[:n_dec] == 0)
        lam = jnp.exp(-decay.astype(f32))
        # padding neither decays nor feeds; position 0 starts from zeros
        da = jnp.where(fresh[:, None], 0.0,
                       jnp.where(real[:, None], lam[None], 1.0))
        dtx = jnp.where(real[:, None], v[:n_dec].astype(f32), 0.0)
        ssm, y = ssm_decode(
            ssm, slots[:n_dec] + slot0, jnp.repeat(da, d, axis=1), dtx,
            k[:n_dec].astype(f32).transpose(0, 2, 1),
            q[:n_dec].astype(f32).transpose(0, 2, 1))
        ys.append(y)
    if t > n_dec:
        _, ts, tp, tv, r = prefill_tiles
        n_i = ts.shape[0]
        live = (jnp.arange(r)[None, :] < tv[:, None]).astype(f32)
        # a head's k, q and v are whole lane tiles of the rows as the
        # projections leave them: the kernel cuts them out itself, and its
        # operand holds the leaf to its layout in every program
        ssm, y = ssd_chunk(
            ssm, *mamba2.tile_rows(ts, tp, slot0, scratch),
            v[n_dec:].reshape(n_i, r, nh * d),
            jnp.broadcast_to(live[..., None], (n_i, r, nh)),
            -decay.astype(f32), k[n_dec:].reshape(n_i, r, nh * d),
            q[n_dec:].reshape(n_i, r, nh * d))
        ys.append(y.reshape(n_i * r, -1))
    y = ys[0] if len(ys) == 1 else jnp.concatenate(ys)
    return _lightning_out(cfg, y, h, lp), ssm


def ragged_forward(cfg: MiniCPMSalaConfig, params, tokens, slots, positions,
                   block_tables, cache, prefill_tiles=None):
    """Flat ragged step: [T] mixed tokens -> ([T, V] logits, cache). Each run
    of layers is scanned where its stack lies (``paged.scan_runs_paged``): a
    sparse layer addressed through its block table, a Lightning layer by its
    slots' rows."""
    from deepspeed_tpu.models.paged import SLOTS, scan_runs_paged

    scratch = cache[SLOTS]["ssm"].shape[1] - 1

    def layer(kind):
        def fn(x, lp, pool, address):
            h = rmsnorm(x, lp["norm"], cfg.rms_norm_eps)
            if kind == "minicpm4":
                o, pool = sparse_attention_ragged(
                    cfg, h, lp["mix"], pool, address, slots, positions,
                    prefill_tiles)
            else:
                o, ssm = lightning_ragged(
                    cfg, h, lp["mix"], lp["decay"], pool[SLOTS]["ssm"],
                    address, scratch, slots, positions, prefill_tiles)
                pool = {**pool, SLOTS: {"ssm": ssm}}
            x = x + o.astype(x.dtype) * cfg.residual_scale
            return _mlp(cfg, x, lp), pool

        return ("block" if kind == "minicpm4" else "slot"), fn

    runs = [(*layer(kind), {**stack, "decay": decays})
            for (kind, _), stack, decays in zip(cfg.runs, params["runs"],
                                                _run_decays(cfg))]
    x = (params["embed"][tokens] * cfg.scale_emb).astype(cache["k"].dtype)
    x, cache = scan_runs_paged(runs, x, cache, block_tables)
    return _head(cfg, params, x), cache


# ------------------------------------------------------------- arithmetic
def _layer_param_count(cfg: MiniCPMSalaConfig, kind: str) -> int:
    """One layer's parameters: the mixer (``W_q``, ``W_z``, ``W_o`` at the
    query heads' width, ``W_k``, ``W_v`` at the key heads', the head norms),
    the MLP and the two norms."""
    d = cfg.hidden_size
    if kind == "minicpm4":
        wide, narrow = cfg.num_heads * cfg.head_dim, cfg.num_kv_heads * cfg.head_dim
        norms = 2 * cfg.head_dim
    else:
        wide = narrow = cfg.lightning_nh * cfg.lightning_head_dim
        norms = 3 * cfg.lightning_head_dim
    return (3 * d * wide + 2 * d * narrow + norms
            + 3 * d * cfg.intermediate_size + 2 * d)


def num_params(cfg: MiniCPMSalaConfig) -> int:
    return (2 * cfg.vocab_size * cfg.hidden_size + cfg.hidden_size
            + sum(_layer_param_count(cfg, kind) for kind in cfg.mixer_types))


def flops_per_token(cfg: MiniCPMSalaConfig, seq_len: int) -> float:
    """Training FLOPs a token: the parameters a token multiplies (the
    embedding is a lookup) plus a sparse layer's attention over the keys it
    keeps at ``seq_len`` on average; the Lightning state's own FLOPs are
    linear in the state and small beside the projections'."""
    active = num_params(cfg) - cfg.vocab_size * cfg.hidden_size
    kept = min(seq_len / 2.0, cfg.kept_keys) if seq_len > cfg.dense_len \
        else seq_len / 2.0
    attn = (12.0 * cfg.layers_of("minicpm4") * cfg.num_heads * cfg.head_dim
            * kept)
    return 6.0 * active + attn


def build(cfg: MiniCPMSalaConfig, ctx: ShardCtx | None = None) -> ModelSpec:
    ctx = ctx or ShardCtx()
    fwd = partial(forward, cfg, ctx=ctx)

    def loss_fn(params, batch, rng=None):
        del rng
        return causal_lm_loss(fwd(params, batch["input_ids"]),
                              batch["input_ids"], batch.get("labels"))

    return ModelSpec(
        name="minicpm_sala",
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
        index_topk=cfg.kept_keys,
        index_blocks=BlockSelection(cfg.dense_len, cfg.block_size,
                                    cfg.kernel_size, cfg.kernel_stride),
        state_kind="lightning",
    )
