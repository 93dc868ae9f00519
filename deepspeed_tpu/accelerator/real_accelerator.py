"""Accelerator implementations and selection.

Role parity with ``accelerator/real_accelerator.py:51`` (``get_accelerator()``):
honors a ``DSTPU_ACCELERATOR`` env override, else probes the JAX backend.
Two concrete backends: TPU (real chips) and CPU (including the
``--xla_force_host_platform_device_count=N`` simulated multi-device mesh used by
tests). GPU-via-JAX also routes through ``TpuAccelerator`` semantics minus
Pallas-TPU kernels.
"""

from __future__ import annotations

import functools
import os

from deepspeed_tpu.accelerator.abstract_accelerator import Accelerator
from deepspeed_tpu.utils.logging import logger


class TpuAccelerator(Accelerator):
    _name = "tpu"

    def communication_backend_name(self) -> str:
        return "xla-ici"

    def device_count(self) -> int:
        import jax

        return jax.local_device_count()

    def global_device_count(self) -> int:
        import jax

        return jax.device_count()

    def devices(self) -> list:
        import jax

        return jax.local_devices()

    def is_bf16_supported(self) -> bool:
        return True

    def is_fp16_supported(self) -> bool:
        return True

    def is_fp8_supported(self) -> bool:
        return True

    def supports_pallas(self) -> bool:
        return True

    # core keys plus the allocator-shape extras (fragmentation = reserved
    # minus in-use; largest_free_block bounds the biggest single allocation
    # that can still succeed) — passed through only where the backend
    # reports them
    _STAT_EXTRAS = ("bytes_reserved", "largest_free_block_bytes",
                    "num_allocs", "bytes_reservable_limit")

    def memory_stats(self, device=None) -> dict[str, int]:
        import jax

        device = device or jax.local_devices()[0]
        stats = getattr(device, "memory_stats", lambda: None)() or {}
        out = {
            "bytes_in_use": stats.get("bytes_in_use", 0),
            "bytes_limit": stats.get("bytes_limit", 0),
            "peak_bytes_in_use": stats.get("peak_bytes_in_use", 0),
        }
        for key in self._STAT_EXTRAS:
            if key in stats:
                out[key] = stats[key]
        return out

    def memory_stats_all_devices(self) -> list[dict[str, int]]:
        """Per-local-device stats, index-aligned with ``devices()``."""
        return [self.memory_stats(d) for d in self.devices()]

    def pinned_memory_sharding(self):
        import jax

        try:
            dev = jax.local_devices()[0]
            return jax.sharding.SingleDeviceSharding(dev, memory_kind="pinned_host")
        except Exception:
            return None


class CpuAccelerator(Accelerator):
    _name = "cpu"

    def communication_backend_name(self) -> str:
        return "gloo-sim"

    def device_count(self) -> int:
        import jax

        return jax.local_device_count()

    def global_device_count(self) -> int:
        import jax

        return jax.device_count()

    def devices(self) -> list:
        import jax

        return jax.local_devices()

    def is_bf16_supported(self) -> bool:
        return True  # emulated on host; numerics preserved

    def is_fp16_supported(self) -> bool:
        return True

    def supports_pallas(self) -> bool:
        return False  # Pallas TPU kernels run in interpret mode only

    def memory_stats(self, device=None) -> dict[str, int]:
        try:
            import resource

            rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
        except Exception:
            rss = 0
        return {"bytes_in_use": rss, "bytes_limit": 0, "peak_bytes_in_use": rss}

    def memory_stats_all_devices(self) -> list[dict[str, int]]:
        # simulated CPU devices share one host process: one stats row
        return [self.memory_stats()]


_accelerator: Accelerator | None = None


def get_accelerator() -> Accelerator:
    global _accelerator
    if _accelerator is not None:
        return _accelerator
    override = os.environ.get("DSTPU_ACCELERATOR")
    if override:
        _accelerator = {"tpu": TpuAccelerator, "cpu": CpuAccelerator}[override.lower()]()
        logger.info(f"Accelerator selected from DSTPU_ACCELERATOR: {override}")
        return _accelerator
    import jax

    platform = jax.default_backend()
    if platform == "cpu":
        _accelerator = CpuAccelerator()
    else:
        # tpu and gpu both get full JAX semantics.
        _accelerator = TpuAccelerator()
        if platform != "tpu":
            _accelerator._name = platform
    return _accelerator


def set_accelerator(acc: Accelerator) -> None:
    global _accelerator
    _accelerator = acc
