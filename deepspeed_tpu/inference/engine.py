"""Inference engine: TP-sharded jitted generation with KV cache.

Role parity with the reference ``inference/engine.py:40 InferenceEngine`` (v1:
TP-sharded kernel-injected generation) — TPU-native shape: the whole
prefill + decode loop is ONE jitted XLA program per (batch, prompt_len,
max_new_tokens) signature; the CUDA-graph capture/replay the reference needs
(``_create_cuda_graph``) is what jit compilation already is on TPU. Tensor
parallelism comes from the same sharding planner as training (AutoTP analog);
the KV cache is a static-shape ring the decode scan updates in place.

Ragged/continuous batching (v2 FastGen analog) lives in
``inference/ragged.py``.
"""

from __future__ import annotations

import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.comm import comm as dist
from deepspeed_tpu.comm.topology import get_topology, topology_initialized
from deepspeed_tpu.config.config import MeshConfig
from deepspeed_tpu.models.api import ModelSpec, ShardCtx
from deepspeed_tpu.parallel.partition import plan_sharding
from deepspeed_tpu.telemetry import get_telemetry
from deepspeed_tpu.utils.logging import log_dist


class InferenceEngine:
    """Greedy / sampled autoregressive generation over a ModelSpec."""

    def __init__(
        self,
        model,
        mp_size: int = 1,
        dtype=jnp.bfloat16,
        params: Any = None,
        checkpoint: str | None = None,
        seed: int = 0,
        quantize_bits: int = 0,
        quantize_block: int = 256,
        quant: str = "off",
    ):
        if topology_initialized():
            self.topo = get_topology()
        else:
            import jax as _jax

            n = len(_jax.devices())
            self.topo = dist.init_distributed(
                MeshConfig(data=n // mp_size, tensor=mp_size)
            )
        self.ctx = ShardCtx(mesh=self.topo.mesh)
        self.spec: ModelSpec = model(self.ctx) if callable(model) else model
        if self.spec.decode_fn is None or self.spec.init_cache_fn is None:
            raise ValueError(f"model {self.spec.name} has no decode/cache support")
        self.dtype = dtype

        self.plan = plan_sharding(
            self.spec.param_logical_axes,
            jax.eval_shape(self.spec.init_fn, jax.random.PRNGKey(0)),
            self.topo,
            zero_stage=0,
            use_tp=self.topo.size("tensor") > 1,
            dim_units=self.spec.logical_dim_units,
        )
        if params is None:
            params = jax.jit(
                self.spec.init_fn, out_shardings=self.plan.param_shardings
            )(jax.random.PRNGKey(seed))
        else:
            params = jax.device_put(params, self.plan.param_shardings)
        # inference weights in compute dtype (reference dtype=half cast)
        self.params = jax.tree_util.tree_map(
            lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x,
            params,
        )
        if checkpoint is not None:
            self.load_checkpoint(checkpoint)
        # ONE low-bit config surface shared with the ragged engine
        # (ops/kvquant.py): the woq component merges with the
        # back-compat quantize_bits arg; '+qcol' quantizes the TP logits
        # all-gather; a KV codec only applies to the paged pool, so it is
        # accepted-but-inert on this dense-cache engine (logged).
        from deepspeed_tpu.ops import kvquant

        parsed = kvquant.parse_quant(quant)
        self._qcol = parsed.qcol and self.topo.size("tensor") > 1
        if parsed.kv is not None:
            log_dist(
                f"InferenceEngine: quant KV codec {parsed.kv.name!r} applies "
                "to the paged pool (RaggedInferenceEngine); inert on the "
                "dense-cache engine", ranks=[0])
        # weight-only quantization (reference inference/quantization/ WOQ):
        # >=2D weights stored int8/int4 blockwise, dequantized just in time
        # per scanned layer (models call ops.quantizer.maybe_dequantize)
        self.quantize_bits = int(quantize_bits) or parsed.woq_bits
        self._quantize_block = quantize_block
        if self.quantize_bits:
            self.params = self._quantize(self.params)
        self._gen_cache: dict = {}
        log_dist(
            f"InferenceEngine: model={self.spec.name} tp={self.topo.size('tensor')} "
            f"dtype={jnp.dtype(dtype).name}"
            + (f" woq=int{self.quantize_bits}" if self.quantize_bits else "")
            + (" qcol" if self._qcol else ""),
            ranks=[0],
        )

    def _maybe_qcol(self, logits):
        """'+qcol': route logits through the quantized TP all-gather (an
        explicit int8-wire shard_map region) instead of GSPMD's implicit fp
        gather. Traced inside the jitted generate/forward programs."""
        if not self._qcol:
            return logits
        from deepspeed_tpu.ops import kvquant

        return kvquant.quantized_logits_all_gather(
            logits, self.topo.mesh, axis="tensor")

    def _quantize(self, params):
        from deepspeed_tpu.ops.quantizer import quantize_params

        return jax.jit(
            lambda p: quantize_params(p, bits=self.quantize_bits,
                                      block=self._quantize_block,
                                      skip=tuple(self.spec.woq_skip))
        )(params)

    def load_checkpoint(self, ckpt_dir: str) -> None:
        """Load params saved by ``Engine.save_checkpoint`` (universal layout).

        On a WOQ engine the checkpoint's dense weights load into a fresh
        dense tree and are re-quantized (the live tree's leaves are int8
        values + scales — dense arrays cannot be mapped onto it)."""
        import os

        from deepspeed_tpu.checkpoint import engine as ckpt
        from deepspeed_tpu.checkpoint import serialization as ser

        from deepspeed_tpu.checkpoint import sharded

        target = self.params
        if getattr(self, "quantize_bits", 0):
            # dense load template: zeros with the plan's shapes/shardings
            # (every value is overwritten by the strict loaders; running the
            # real init would waste a full model's compute + memory)
            abstract = jax.eval_shape(self.spec.init_fn, jax.random.PRNGKey(0))
            target = jax.tree_util.tree_map(
                lambda s, sh: jax.device_put(
                    jnp.zeros(s.shape,
                              self.dtype if jnp.issubdtype(s.dtype, jnp.floating)
                              else s.dtype), sh),
                abstract, self.plan.param_shardings)

        tag = ckpt.latest_tag(ckpt_dir)
        model_dir = os.path.join(ckpt_dir, tag) if tag else ckpt_dir
        if sharded.is_sharded(model_dir, "model"):
            # fragments re-placed straight under the inference plan/dtype
            loaded = sharded.load_sharded(target, model_dir, "model")
        else:
            arrays = ser.load_arrays(os.path.join(model_dir, "model.npz"))
            host = ser.arrays_to_tree(
                jax.tree_util.tree_map(np.asarray, target), arrays
            )
            loaded = jax.device_put(host, self.plan.param_shardings)
        if getattr(self, "quantize_bits", 0):
            loaded = self._quantize(loaded)
        self.params = loaded

    # ------------------------------------------------------------------ generate
    def _build_generate(self, batch: int, prompt_len: int, max_new: int,
                        sample: bool, use_penalty: bool, has_tk: bool,
                        has_tp: bool):
        decode = self.spec.decode_fn
        init_cache = self.spec.init_cache_fn
        total = prompt_len + max_new

        def generate_fn(params, tokens, rng, temperature, top_k, top_p,
                        rep_pen):
            from deepspeed_tpu.inference.sampling import (
                sample_tokens,
                update_seen,
            )

            cache = init_cache(batch, total, self.dtype)
            logits, cache = decode(params, tokens, cache, 0)
            last = self._maybe_qcol(
                logits[:, prompt_len - 1]).astype(jnp.float32)
            vocab = last.shape[-1]
            # occurrence mask over the prompt (HF repetition_penalty
            # semantics: penalize everything in the context)
            seen0 = (jnp.zeros((batch, vocab), jnp.bool_)
                     .at[jnp.arange(batch)[:, None], tokens].set(True)
                     if use_penalty else jnp.zeros((batch, 1), jnp.bool_))

            def pick(logits_f, r, seen):
                if not sample and not use_penalty:
                    return jnp.argmax(logits_f, axis=-1).astype(jnp.int32)
                toks, _ = sample_tokens(
                    logits_f, r,
                    temperature if sample else jnp.float32(0.0),
                    # None compiles the top-k/top-p sorts OUT when disabled
                    # (the flags are static in the cache key)
                    top_k=top_k if has_tk else None,
                    top_p=top_p if has_tp else None,
                    repetition_penalty=rep_pen if use_penalty else None,
                    seen_mask=seen if use_penalty else None)
                return toks

            def step(carry, i):
                last, cache, seen = carry
                r = jax.random.fold_in(rng, i)
                tok = pick(last, r, seen)
                if use_penalty:
                    seen = update_seen(seen, tok)
                logits, cache = decode(params, tok[:, None], cache, prompt_len + i)
                return (self._maybe_qcol(logits[:, 0]).astype(jnp.float32),
                        cache, seen), tok

            (_, _, _), toks = jax.lax.scan(
                step, (last, cache, seen0), jnp.arange(max_new))
            return toks.T  # [B, max_new]

        return jax.jit(generate_fn)

    def generate(self, input_ids, max_new_tokens: int = 64, temperature: float = 0.0,
                 seed: int = 0, top_k: int = 0, top_p: float = 1.0,
                 repetition_penalty: float = 1.0):
        """[B, T] prompt -> [B, T + max_new_tokens] (greedy when temperature=0;
        ``top_k``/``top_p``/``repetition_penalty`` follow the reference
        generate surface, ``inference/engine.py:586 _generate`` forwarding HF
        sampling kwargs — see ``inference/sampling.py``).

        Each (B, T, N, sampled?, penalized?) signature compiles once and
        replays (CUDA-graph parity); the sampling VALUES are traced, so
        changing temperature/top_k/top_p never recompiles."""
        input_ids = np.asarray(input_ids)
        b, t = input_ids.shape
        sample = temperature > 0.0
        use_penalty = repetition_penalty != 1.0
        has_tk, has_tp = top_k > 0, top_p < 1.0
        key = (b, t, max_new_tokens, sample, use_penalty, has_tk, has_tp)
        telemetry = get_telemetry()
        t0 = time.perf_counter() if telemetry.enabled else 0.0
        compiled = key in self._gen_cache
        if not compiled:
            self._gen_cache[key] = self._build_generate(
                b, t, max_new_tokens, sample, use_penalty, has_tk, has_tp)
        toks = self._gen_cache[key](
            self.params,
            jnp.asarray(input_ids),
            jax.random.PRNGKey(seed),
            jnp.float32(max(temperature, 1e-6)),
            jnp.int32(top_k),
            jnp.float32(top_p),
            jnp.float32(repetition_penalty),
        )
        toks = np.asarray(toks)
        if telemetry.enabled:
            # the whole prefill+decode program is one dispatch: TTFT/per-token
            # breakdown belongs to the ragged engine; here the span carries
            # batch shape + whether this call paid the compile
            telemetry.emit_span(
                "inference/generate", time.perf_counter() - t0,
                batch=b, prompt_tokens=t, new_tokens=max_new_tokens,
                cached_program=compiled)
            telemetry.counter(
                "inference_tokens_generated_total", "tokens generated").inc(
                    b * max_new_tokens)
        return np.concatenate([input_ids, toks], axis=1)

    def forward(self, input_ids):
        """Plain logits forward (reference ``engine.forward:557``); jitted —
        sharding constraints inside the model require a compiled context."""
        if not hasattr(self, "_fwd_jit"):
            self._fwd_jit = jax.jit(self.spec.forward_fn)
        return self._fwd_jit(self.params, jnp.asarray(input_ids))

    __call__ = forward


def init_inference(model, config: dict | None = None, **kwargs):
    """Reference ``deepspeed.init_inference`` (``__init__.py:328``)."""
    config = dict(config or {})
    config.update(kwargs)
    tp = config.get("tensor_parallel", {})
    mp_size = tp.get("tp_size", config.get("mp_size", 1)) if isinstance(tp, dict) else int(tp)
    dtype_str = str(config.get("dtype", "bf16")).replace("torch.", "").replace(
        "float16", "fp16")
    dtype = {"bf16": jnp.bfloat16, "fp16": jnp.float16, "fp32": jnp.float32}.get(
        dtype_str, jnp.bfloat16)
    # reference WOQ knobs: dtype=torch.int8 or quant: {weight: {num_bits}}
    bits = 0
    if dtype_str in ("int8", "qint8"):
        bits = 8
    quant = config.get("quant")
    quant_str = "off"
    if isinstance(quant, str):  # kvquant grammar: e.g. "int8+woq8+qcol"
        quant_str = quant
    elif isinstance(quant, dict) and quant.get("enabled", True):
        bits = int((quant.get("weight") or {}).get("num_bits", bits or 8))
    return InferenceEngine(
        model,
        mp_size=mp_size,
        dtype=dtype,
        params=config.get("params"),
        checkpoint=config.get("checkpoint"),
        quantize_bits=int(config.get("quantize_bits", bits)),
        quantize_block=int(config.get("quantize_block", 256)),
        quant=quant_str,
    )
