"""SDAR (JetLM SDAR-30B-A3B-Chat, ``sdar_moe``), plain: the published layer and
the release's generation by diffusion over blocks, in straight ``jax.numpy``.

No kernels, no cache, no batching, nothing imported from the program. Layer on
its input ``x`` [S, D], blocks of ``B = cfg.block_length`` positions:

    h   = rmsnorm(x, g_attn)
    q, k, v = h W_q, h W_k, h W_v        32 query heads over 4 KV heads of 128
    q   = rmsnorm_over_head(q, g_q);  k = rmsnorm_over_head(k, g_k)
    q, k rotated (theta, all lanes, half-split pairs)
    allowed(i, j) = floor(j / B) <= floor(i / B)          block-causal
    x1  = x + softmax(q k / sqrt(128)) v W_o
    h2  = rmsnorm(x1, g_ffn)
    p   = softmax(h2 W_r) in float32; top = the 8 largest; w = p[top] / sum
    out = x1 + sum_e w_e (silu(h2 W_gate,e) * (h2 W_up,e)) W_down,e
    logits_i = rmsnorm(x_L, g_f)_i W_head      UNSHIFTED: row i scores token i

**Generation** (greedy, ``T = cfg.denoise_steps`` passes a block, ``n = B / T``
positions unmasked a pass): a block starts masked (``cfg.mask_token_id``);
pass ``s`` runs the block against the finished blocks before it and itself as
it stands, takes ``argmax`` at the masked positions and unmasks ``n`` of them;
then the finished block is what later blocks read. Under the ``sequential``
rule the order is fixed: pass ``s`` unmasks offsets ``s n .. s n + n - 1``,
so the state in which token ``i`` was chosen follows from the tokens alone:
its block holds the true tokens at offsets ``< s n`` and ``MASK`` from there
on, ``s = (i mod B) // n``.

``denoise_logits`` replays exactly that, for every position at once, in two
kinds of stream: the CLEAN stream (the tokens as they are, every block
finished: its K and V are what a later block reads) and, for each pass ``s``,
a NOISY stream (every block as it stands at the start of pass ``s``), whose
rows see the clean K and V of the blocks before their own and the noisy K and
V of their own block. Row ``i`` of the result is the noisy stream of ITS pass:
the logits that chose token ``i``. No row reads an id at or after its own
pass's first masked offset, so zeros behind the served tokens and a last
block that was cut short are inert.

``forward`` is that array **shifted by one row**, ``forward[i - 1] =
denoise_logits[i]``: the benchmark's check (``serve_cell.ServeRig.check``)
was written for next-token models and reads row ``i - 1`` as the logits that
chose token ``i``. The shift is the harness's convention, not the model's.

Memory (beside 10 GB of resident bf16 weights): streams one after another,
layers one at a time, experts one at a time (an all-experts intermediate is
805 MB at 2,048 rows), attention ``Q_BLOCK`` query rows at a time, and ONE
head product over the rows kept (each position's from its own pass).

Also the arithmetic of the model that metrics divide by.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

Q_BLOCK = 512


def _rms(x, w, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * w


def _rope(x, positions, theta):
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def _attention(q, keys, values, allowed, dtype):
    """GQA, ``Q_BLOCK`` query rows at a time over every key under
    ``allowed(query positions [Q, 1]) -> [Q, K]``. q [S,Hq,D], keys / values
    [K,Hkv,D]."""
    s, hq, d = q.shape
    rep = hq // keys.shape[1]
    keys, values = jnp.repeat(keys, rep, axis=1), jnp.repeat(values, rep, axis=1)

    def block(q0):
        qb = jax.lax.dynamic_slice_in_dim(q, q0, Q_BLOCK, axis=0)
        scores = jnp.einsum("qhd,khd->hqk", qb, keys) / jnp.sqrt(
            jnp.asarray(d, dtype))
        ok = allowed((q0 + jnp.arange(Q_BLOCK))[:, None])
        scores = jnp.where(ok[None], scores, -jnp.inf)
        p = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(dtype)
        return jnp.einsum("hqk,khd->qhd", p, values)

    return jax.lax.map(block, jnp.arange(0, s, Q_BLOCK)).reshape(s, hq, d)


def _moe(cfg, h, lp, dtype):
    probs = jax.nn.softmax(h.astype(jnp.float32)
                           @ lp["router"].astype(jnp.float32), axis=-1)
    top_w, top_i = jax.lax.top_k(probs, cfg.top_k)
    top_w = top_w / top_w.sum(-1, keepdims=True)
    # combine[t, e]: the renormalised weight where e is among t's picks
    combine = (jax.nn.one_hot(top_i, cfg.num_experts, dtype=jnp.float32)
               * top_w[..., None]).sum(1)

    def expert(acc, we):
        wg, wu, wd, c = we
        y = (jax.nn.silu(h @ wg.astype(dtype)) * (h @ wu.astype(dtype))) \
            @ wd.astype(dtype)
        return acc + y * c[:, None].astype(dtype), None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                          (lp["w_gate"], lp["w_up"], lp["w_down"], combine.T))
    return out


def _stream(cfg, params, ids, dtype, clean=None, in_block=None):
    """One stream through every layer: ``(final-normed rows [S, D], (K, V)
    of every layer [L, S, Hkv, D])``. ``clean`` None: the clean stream, a row
    sees the rows of its own and of earlier blocks. ``clean = (K, V)``: a
    noisy stream, a row sees the CLEAN rows of earlier blocks and the rows of
    ITS OWN stream in its own block. ``in_block(i, j)``: who sees whom inside
    a block (None: everyone; a planted fault hands a causal one in)."""
    s = ids.shape[0]
    hq, hkv, hd, blk = (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
                        cfg.block_length)
    pos = jnp.arange(s)
    kpos = pos[None, :]
    x = params["embed"][ids].astype(dtype)

    def own(i):      # the row's own block, under ``in_block``
        same = kpos // blk == i // blk
        return same if in_block is None else same & in_block(i, kpos)

    def layer(x, lp_kv):
        lp = lp_kv[0]
        h = _rms(x, lp["attn_norm"].astype(dtype), cfg.rms_norm_eps)
        q = _rms((h @ lp["wq"].astype(dtype)).reshape(s, hq, hd),
                 lp["q_norm"].astype(dtype), cfg.rms_norm_eps)
        k = _rms((h @ lp["wk"].astype(dtype)).reshape(s, hkv, hd),
                 lp["k_norm"].astype(dtype), cfg.rms_norm_eps)
        v = (h @ lp["wv"].astype(dtype)).reshape(s, hkv, hd)
        q, k = _rope(q, pos, cfg.rope_theta), _rope(k, pos, cfg.rope_theta)
        if clean is None:
            o = _attention(q, k, v,
                           lambda i: (kpos // blk < i // blk) | own(i), dtype)
        else:
            o = _attention(
                q, jnp.concatenate([lp_kv[1], k]),
                jnp.concatenate([lp_kv[2], v]),
                lambda i: jnp.concatenate(
                    [kpos // blk < i // blk, own(i)], axis=1), dtype)
        x = x + o.reshape(s, hq * hd) @ lp["wo"].astype(dtype)
        h = _rms(x, lp["mlp_norm"].astype(dtype), cfg.rms_norm_eps)
        return x + _moe(cfg, h, lp, dtype), (k, v)

    xs = (params["layers"],) + (() if clean is None else tuple(clean))
    x, kv = jax.lax.scan(layer, x, xs)
    return _rms(x, params["final_norm"].astype(dtype), cfg.rms_norm_eps), kv


def denoise_logits(cfg, params, ids, dtype=jnp.float32, steps=None,
                   order=None, in_block=None, commit=True):
    """``ids`` [S] -> [S, vocab]: row ``i`` is the logits that chose token
    ``i`` under the ``sequential`` rule at ``steps`` (``cfg.denoise_steps``)
    passes a block (module text).

    ``order`` [S] int: the pass of its block in which each position WAS
    unmasked, for a trajectory the tokens do not determine (a rule that ranks
    by confidence; a first block that opened with the prompt's remainder:
    those positions, never masked, are ``-1``); the streams are then one a
    pass that occurs (``steps`` of them where it is given: a traced
    ``order`` cannot say). ``in_block`` and ``commit`` plant faults for the
    controls: a mask inside the block, and ``commit=False`` a cache that kept
    each block as it stood in its LAST DENOISE pass (the commit pass left
    out) in place of the finished block."""
    s = ids.shape[0]
    pad = -s % Q_BLOCK
    ids = jnp.pad(ids, (0, pad))
    if order is None:
        steps = cfg.denoise_steps if steps is None else steps
        order = (jnp.arange(s + pad) % cfg.block_length) // (
            cfg.block_length // steps)
    else:
        steps = int(max(order)) + 1 if steps is None else steps
        order = jnp.pad(jnp.asarray(order), (0, pad), constant_values=steps)

    def noisy(step):      # every block as it stands when pass ``step`` starts
        return jnp.where(order >= step, cfg.mask_token_id, ids)

    _, clean = _stream(cfg, params, ids if commit else noisy(steps - 1),
                       dtype, in_block=in_block)
    rows = jnp.zeros((s + pad, cfg.hidden_size), dtype)
    for step in range(steps):
        x, _ = _stream(cfg, params, noisy(step), dtype, clean, in_block)
        rows = jnp.where((order == step)[:, None], x, rows)
    return (rows @ params["lm_head"].astype(dtype))[:s]


def forward(cfg, params, ids, dtype=jnp.float32):
    """``denoise_logits`` shifted by one row, the harness's convention: row
    ``i - 1`` holds the logits that chose token ``i`` (the last row zeros)."""
    logits = denoise_logits(cfg, params, ids, dtype)
    return jnp.concatenate([logits[1:], jnp.zeros_like(logits[:1])])


# ------------------------------------------------------------- arithmetic
def _layer_params(cfg, experts: int) -> int:
    d, f, hd = cfg.hidden_size, cfg.moe_intermediate_size, cfg.head_dim
    return (d * hd * (2 * cfg.num_heads + 2 * cfg.num_kv_heads) + 2 * hd
            + d * cfg.num_experts + 2 * d + 3 * experts * d * f)


def num_params(cfg) -> int:
    d = cfg.hidden_size
    return (2 * cfg.vocab_size * d + d
            + cfg.num_layers * _layer_params(cfg, cfg.num_experts))


def active_params(cfg) -> int:
    """Parameters a row's forward pass multiplies by: ``top_k`` experts a
    layer, attention, router, head; the embedding is a lookup."""
    return (cfg.vocab_size * cfg.hidden_size
            + cfg.num_layers * _layer_params(cfg, cfg.top_k))


def train_flops_per_token(cfg, seq_len: int) -> float:
    return (6.0 * active_params(cfg) + 12.0 * cfg.num_layers * cfg.num_heads
            * cfg.head_dim * seq_len / 2.0)


def weight_bytes(cfg, bytes_per_param: int = 2) -> int:
    """Bytes a step must read: every expert's weights, whatever the routing
    of more than a few rows; the embedding is a lookup."""
    return (num_params(cfg) - cfg.vocab_size * cfg.hidden_size) * bytes_per_param


def kv_bytes_per_token(cfg, itemsize: int = 2) -> int:
    """K and V a cached token costs over the layers (head 128, not ``hidden
    / heads``): what ``kv_tokens`` / ``dec_kv_tokens`` multiply, once a
    sequence and PASS."""
    return cfg.num_layers * 2 * cfg.num_kv_heads * cfg.head_dim * itemsize


def attn_flops_per_pair(cfg) -> int:
    """FLOPs of one query x key pair over the layers (``attn_pairs``)."""
    return cfg.num_layers * 4 * cfg.num_heads * cfg.head_dim
