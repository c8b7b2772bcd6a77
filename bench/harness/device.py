"""The chip: the check that one is there, the compile cache, the compile
clock, memory, and the peaks the readers divide by."""
from __future__ import annotations

import json
import os
from typing import Any, Dict

from .cells import BENCH, CHECKOUT

CACHE_DIR = CHECKOUT / ".jax_cache"


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def use_checkout_cache() -> str:
    """Point JAX's persistent compile cache at ``<checkout>/.jax_cache``
    before JAX is imported. The path is fixed: it is part of the cache's
    key, so a directory that moved would never hit. The program's own
    ``compile_cache.enable()`` reads the same variable."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    return str(CACHE_DIR)


def enable_cache() -> None:
    import jax
    from repro.launch import compile_cache
    compile_cache.enable()
    # every program, however quick to compile, comes from the cache in a
    # warm run, so set-up does the same work run after run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def require_chips(n: int) -> Dict[str, Any]:
    """The device record, or NoAccelerator when the first device is not a
    TPU or fewer than ``n`` are visible. There is no CPU fallback."""
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise NoAccelerator(f"needs a TPU; JAX found {dev.platform!r}")
    if len(devices) < n:
        raise NoAccelerator(f"the cell asks for {n} chips; {len(devices)} "
                            "visible")
    return {"platform": dev.platform, "kind": dev.device_kind, "count": n}


def memory_peak_bytes(n: int) -> int:
    """Peak bytes in use on the fullest of the first ``n`` devices (one
    process is one run, so the allocator's peak is the run's)."""
    import jax
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices()[:n])


def peaks(device_kind: str) -> Dict[str, float]:
    """Published peaks of one chip of this kind (``peaks.json``). A kind
    that is not in the table is an error, never a default."""
    with open(BENCH / "peaks.json") as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"peaks.json has {sorted(table)}")
    return table[device_kind]


class CompileClock:
    """Counts XLA backend compiles from JAX's monitoring events. A program
    served from the persistent cache emits none."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.count = 0

        def listen(name, secs, **_):
            if name == self.EVENT:
                self.count += 1
        jax.monitoring.register_event_duration_secs_listener(listen)
