"""TpuDeviceManager — pool sizing and device init.

Reference analog: GpuDeviceManager.initializeGpuAndMemory / initializeRmm
(SURVEY.md §2.3): picks the device, sizes the RMM pool from
``spark.rapids.memory.gpu.allocFraction`` minus a reserve for non-pool
allocations.  Here the "pool" is the logical HBM budget the spill framework
enforces; the reserve mirrors the reference's headroom for framework
temporaries (there: CUDA context/cuDF scratch; here: XLA scratch and the
compiled programs' workspaces).
"""
from __future__ import annotations

import threading
from typing import Optional

from spark_rapids_tpu.config import (
    HBM_POOL_FRACTION,
    HBM_RESERVE,
    TpuConf,
    conf,
)

TEST_DEVICE_MEMORY = conf("spark.rapids.tpu.test.deviceMemoryBytes").doc(
    "Test override for the physical device memory size the pool is computed "
    "from (the XLA CPU backend reports no memory stats).").internal(
).bytes_conf(0)


_CPU_BACKEND_MEMORY = 16 << 30   # the XLA CPU backend reports no stats


def _physical_hbm_bytes() -> int:
    """Device 0's memory size as the runtime reports it.  Only the CPU
    backend (tests) may go without: on an accelerator a missing
    ``memory_stats()`` is an error, never an assumed 16 GiB."""
    import jax

    dev = jax.local_devices()[0]
    stats = dev.memory_stats()
    if stats and "bytes_limit" in stats:
        return int(stats["bytes_limit"])
    if dev.platform == "cpu":
        return _CPU_BACKEND_MEMORY
    raise RuntimeError(
        f"{dev.platform} device {dev.device_kind!r} reports no "
        f"memory_stats()['bytes_limit']: the HBM pool cannot be sized "
        f"(set spark.rapids.tpu.test.deviceMemoryBytes to override)")


class TpuDeviceManager:
    """Computes and holds the HBM pool budget (thread-safe singleton)."""

    def __init__(self, tpu_conf: Optional[TpuConf] = None):
        c = tpu_conf or TpuConf()
        override = c.get(TEST_DEVICE_MEMORY)
        physical = override or _physical_hbm_bytes()
        reserve = c.get(HBM_RESERVE)
        frac = c.get(HBM_POOL_FRACTION)
        self.physical_bytes = physical
        self.pool_bytes = max(int(physical * frac) - reserve, 64 << 20) \
            if not override else override
        self.reserve_bytes = reserve

    def describe(self) -> str:
        return (f"TpuDeviceManager pool={self.pool_bytes >> 20}MiB "
                f"physical={self.physical_bytes >> 20}MiB "
                f"reserve={self.reserve_bytes >> 20}MiB")


_lock = threading.Lock()
_manager: Optional[TpuDeviceManager] = None


def get_device_manager(tpu_conf: Optional[TpuConf] = None) -> TpuDeviceManager:
    global _manager
    with _lock:
        if _manager is None or tpu_conf is not None:
            _manager = TpuDeviceManager(tpu_conf)
        return _manager


def reset_device_manager() -> None:
    global _manager
    with _lock:
        _manager = None
