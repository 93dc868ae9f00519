"""GPT-2, plain: the published forward pass in straight ``jax.numpy``.

No kernels, no cache, no batching tricks, nothing imported from the program.
LayerNorm with bias, learned positions, fused-free QKV, causal softmax
attention, GELU (tanh form, ``gelu_new``), tied head. Parameters are the
program's tree (``wte``, ``wpe``, stacked ``layers``); each layer's weights are
converted to ``dtype`` as the layer runs, so a float32 reference never holds a
float32 copy of the whole model.

Also the arithmetic of the model that metrics divide by, kept here so that no
later PR can move a denominator: parameter count, FLOPs per trained token,
bytes of weights.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _ln(x, g, b, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def forward(cfg, params, ids, dtype=jnp.float32):
    """``ids`` [S] -> logits [S, vocab] in ``dtype`` arithmetic."""
    s = ids.shape[0]
    nh, hd = cfg.num_heads, cfg.hidden_size // cfg.num_heads
    x = (params["wte"][ids] + params["wpe"][:s]).astype(dtype)
    mask = jnp.tril(jnp.ones((s, s), bool))

    def layer(x, lp):
        lp = jax.tree_util.tree_map(lambda w: w.astype(dtype), lp)
        h = _ln(x, lp["ln1_g"], lp["ln1_b"], cfg.layer_norm_eps)
        q = (h @ lp["wq"] + lp["bq"]).reshape(s, nh, hd)
        k = (h @ lp["wk"] + lp["bk"]).reshape(s, nh, hd)
        v = (h @ lp["wv"] + lp["bv"]).reshape(s, nh, hd)
        scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.asarray(hd, dtype))
        scores = jnp.where(mask[None], scores, -jnp.inf)
        p = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(dtype)
        o = jnp.einsum("hqk,khd->qhd", p, v).reshape(s, nh * hd)
        x = x + o @ lp["wo"] + lp["bo"]
        h = _ln(x, lp["ln2_g"], lp["ln2_b"], cfg.layer_norm_eps)
        h = jax.nn.gelu(h @ lp["w_in"] + lp["b_in"], approximate=True)
        return x + h @ lp["w_out"] + lp["b_out"], None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    x = _ln(x, params["lnf_g"].astype(dtype), params["lnf_b"].astype(dtype),
            cfg.layer_norm_eps)
    return x @ params["wte"].astype(dtype).T


def num_params(cfg) -> int:
    d, f = cfg.hidden_size, 4 * cfg.hidden_size
    per_layer = 4 * d * d + 4 * d + 2 * d * f + d + f + 4 * d
    return (cfg.vocab_size * d + cfg.max_seq_len * d
            + cfg.num_layers * per_layer + 2 * d)


def active_params(cfg) -> int:
    """Parameters a token's forward pass multiplies by (dense: all)."""
    return num_params(cfg)


def train_flops_per_token(cfg, seq_len: int) -> float:
    """6 N + causal attention (12 L d s / 2); recomputation not counted."""
    return (6.0 * num_params(cfg)
            + 12.0 * cfg.num_layers * cfg.hidden_size * seq_len / 2.0)


def weight_bytes(cfg, bytes_per_param: int = 2) -> int:
    return num_params(cfg) * bytes_per_param
