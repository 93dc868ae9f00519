"""Token sampling: temperature / top-k / top-p / repetition penalty.

Role parity with the reference generate surface
(``deepspeed/inference/engine.py:586 _generate`` forwards HF sampling
kwargs — do_sample, temperature, top_k, top_p, repetition_penalty — to the
wrapped module's ``generate``). Here sampling is a jittable primitive the
engines call INSIDE their compiled decode loops, so sampled multi-step decode
(hybrid rollouts, ragged run-ahead) needs no host round trip per token.

All controls are per-row arrays, so one compiled program serves a batch
mixing greedy and sampled requests (the ragged engine's per-request params).

Semantics (matching the HF/reference processors):
- ``temperature`` <= 0 means greedy (argmax); otherwise logits /= temperature.
- ``top_k`` 0 disables; otherwise only the k highest logits stay.
- ``top_p`` >= 1 disables; otherwise the smallest prefix of the
  descending-sorted distribution with cumulative probability >= top_p stays
  (the highest-probability token always stays).
- ``repetition_penalty`` 1.0 disables; otherwise seen tokens' logits are
  divided by the penalty when positive and multiplied when negative (the CTRL
  paper rule HF implements). "Seen" comes from a per-row occurrence mask the
  caller maintains (prompt + generated so far).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_NEG = -1e30


def apply_repetition_penalty(logits, seen_mask, penalty):
    """CTRL-rule repetition penalty. ``logits`` [T, V] fp32; ``seen_mask``
    [T, V] bool/int (nonzero = token occurred in the row's context);
    ``penalty`` [T] fp32 (1.0 = off)."""
    pen = penalty[:, None]
    seen = seen_mask.astype(jnp.bool_)
    penalized = jnp.where(logits > 0, logits / pen, logits * pen)
    return jnp.where(seen & (pen != 1.0), penalized, logits)


def _mask_top_k(logits, top_k):
    """Keep the per-row ``top_k`` highest logits (0 = keep all). ``top_k``
    [T] int32 — per-row variable k via the k-th order statistic."""
    v = logits.shape[-1]
    k = jnp.where(top_k <= 0, v, jnp.minimum(top_k, v)).astype(jnp.int32)
    sorted_desc = jnp.sort(logits, axis=-1)[:, ::-1]
    kth = jnp.take_along_axis(sorted_desc, (k - 1)[:, None], axis=-1)
    return jnp.where(logits >= kth, logits, _NEG)


def _mask_top_p(logits, top_p):
    """Nucleus filtering. ``top_p`` [T] fp32 (>= 1 disables). The smallest
    descending-probability prefix with cumulative mass >= top_p survives."""
    sorted_desc = jnp.sort(logits, axis=-1)[:, ::-1]
    probs = jax.nn.softmax(sorted_desc, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    # position i survives if the mass BEFORE it is < top_p (so the first
    # token always survives and the prefix reaching top_p is included)
    prev = cum - probs
    keep_sorted = prev < top_p[:, None]
    # threshold value: smallest surviving logit per row
    n_keep = jnp.sum(keep_sorted, axis=-1)  # >= 1
    thr = jnp.take_along_axis(sorted_desc, (n_keep - 1)[:, None], axis=-1)
    disabled = (top_p >= 1.0)[:, None]
    return jnp.where(disabled | (logits >= thr), logits, _NEG)


def sample_tokens(logits, rng, temperature, top_k=None, top_p=None,
                  repetition_penalty=None, seen_mask=None):
    """Pick next tokens for a batch of rows.

    ``logits`` [T, V] (any float dtype); per-row controls broadcast from
    scalars. ``rng`` is either one PRNG key (shared noise source for the
    batch) or a [T, 2] array of per-row keys — per-row keys make a row's
    draw a function of that row alone, which is what batch-invariant
    (prefix-cache-reproducible) sampling needs. Returns (tokens [T] int32,
    logprobs [T] fp32) — the logprob is of the chosen token under the FINAL
    (tempered+filtered) distribution, which is what an RLHF behavior policy
    must record; greedy rows report the untempered log-softmax.
    """
    logits = logits.astype(jnp.float32)
    t = logits.shape[0]
    as_row = lambda x, d: (jnp.broadcast_to(jnp.asarray(x, d), (t,))  # noqa: E731
                           if x is not None else None)
    temperature = as_row(temperature, jnp.float32)
    top_k = as_row(top_k, jnp.int32)
    top_p = as_row(top_p, jnp.float32)
    repetition_penalty = as_row(repetition_penalty, jnp.float32)

    if repetition_penalty is not None and seen_mask is not None:
        logits = apply_repetition_penalty(logits, seen_mask,
                                          repetition_penalty)
    greedy = temperature <= 0.0
    greedy_lp = jax.nn.log_softmax(logits, axis=-1)
    filt = logits / jnp.maximum(temperature, 1e-6)[:, None]
    if top_k is not None:
        filt = _mask_top_k(filt, top_k)
    if top_p is not None:
        filt = _mask_top_p(filt, top_p)
    rng = jnp.asarray(rng)
    if rng.ndim == 2:  # [T, 2] per-row keys
        sampled = jax.vmap(
            lambda r, lg: jax.random.categorical(r, lg)
        )(rng, filt).astype(jnp.int32)
    else:
        sampled = jax.random.categorical(rng, filt, axis=-1).astype(jnp.int32)
    greedy_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    toks = jnp.where(greedy, greedy_tok, sampled)
    lp = jnp.where(greedy,
                   jnp.take_along_axis(greedy_lp, greedy_tok[:, None],
                                       axis=-1)[:, 0],
                   jnp.take_along_axis(jax.nn.log_softmax(filt, axis=-1),
                                       toks[:, None], axis=-1)[:, 0])
    return toks, lp


def per_request_keys(root, seeds, gen_idx):
    """Derive [T, 2] per-row sampling keys from per-request seeds and
    generated-token indices: ``fold_in(fold_in(root, seed), g)``.

    The draw for token ``g`` of a request depends only on (root, seed, g) —
    never on batch composition, dispatch chunking, or engine history — so a
    sampled generation replays identically whether it runs cold, hits the
    prefix cache, or lands in a different dispatch mode."""
    def one(s, g):
        return jax.random.fold_in(jax.random.fold_in(root, s), g)
    return jax.vmap(one)(seeds, gen_idx)


def keys_for_positions(root, seeds, positions, prompt_lens):
    """Per-row sampling keys derived from DEVICE-RESIDENT scheduler rows.

    The token produced by feeding position ``p`` of a request is its
    generated-token index ``p - prompt_len + 1`` (a decode row feeds
    ``generated[p - prompt_len]`` and yields the next one; the prompt's
    final row, ``p = prompt_len - 1``, yields index 0). Computing the index
    on device from the persistent position/prompt-len rows keeps the key
    derivation batch-invariant — identical to ``per_request_keys`` with a
    host-computed ``gen_idx`` — without staging any host array."""
    return per_request_keys(root, seeds, positions - prompt_lens + 1)


def update_seen(seen_mask, tokens):
    """Mark freshly emitted tokens in the occurrence mask ([T, V] x [T])."""
    return seen_mask.at[jnp.arange(tokens.shape[0]), tokens].set(True)

