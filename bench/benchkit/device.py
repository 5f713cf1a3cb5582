"""The chip the run is on: presence, identity, memory, compile cache."""

from __future__ import annotations

import os
from pathlib import Path
from typing import Dict

from .spec import ROOT


class NoAccelerator(RuntimeError):
    pass


def use_compile_cache(root: Path = ROOT) -> str:
    """JAX's persistent compilation cache at a fixed path in the
    checkout (``JAX_COMPILATION_CACHE_DIR`` wins where it is set), with
    every program cached, so only a checkout's first run compiles."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def require_accelerator(chips: int) -> None:
    """Fail unless JAX's devices are TPUs, at least ``chips`` of them."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoAccelerator(f"no TPU: JAX found {devs[0].platform!r} devices")
    if len(devs) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX found "
                            f"{len(devs)}")


def info(chips: int) -> Dict:
    import jax

    devs = jax.devices()[:chips]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes(chips: int) -> int:
    """Peak bytes in use on the fullest chip (0 where the backend keeps
    no count, as the CPU does)."""
    import jax

    peaks = []
    for d in jax.devices()[:chips]:
        st = d.memory_stats() or {}
        peaks.append(int(st.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0
