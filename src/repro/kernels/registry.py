"""Kernel registry: one table from (mode, backend, fused, layout) to the
kernel that implements it, with capability metadata.

This replaces the duplicated mode x backend if/elif ladders that used to
live inside ``ops.packed_matmul`` and ``ops.fused_qmm``: kernels register
themselves once, dispatch is a dict lookup, and benchmarks / tests / the
serving engine can *enumerate* what exists instead of hard-coding mode
lists.  New kernels plug in by registering a new entry — no dispatch
code changes (the fused-im2col conv kernels and the dense-backend MXU
fusion kernels both landed exactly this way).

``layout`` names the *operand layout* the kernel consumes:

* ``"gemm"`` (default) — A is an explicit (m, k) activation matrix;
* ``"im2col_fused"`` — A is the raw (B, H, W, Cin) activation tensor and
  the kernel folds im2col patch extraction into its A-operand load path
  (kernels/conv_fused.py); ``conv2d_packed`` dispatches here.

Normalized kernel signatures (planes are tuples of uint32 bit-plane
arrays — 1 plane for binary operands, 2 (plus, minus) for ternary):

* gemm, unfused (``fused=False``) — the integer core:
      fn(a_planes, b_planes, k_valid, *, interpret, tiles=None)
          -> int32 (m, n)
* gemm, fused (``fused=True``) — core + eq. (2) scale/bias epilogue:
      fn(a_planes, b_planes, k_valid, row_scale, col_scale, bias, *,
         interpret, tiles=None) -> float32 (m, n)
* im2col_fused (always ``fused=True``) — patch extraction + quantize +
  pack + core + epilogue in one kernel/trace:
      fn(x, b_planes, geometry, stride, padding, stats, col_scale,
         bias, *, interpret, tiles=None) -> float32 (B, OH, OW, Cout)

``tiles`` (a ``TileConfig``) overrides the kernel's blocking; ``None``
resolves it from the autotuning plan cache at trace time (tuned plan on
a hit, ``DEFAULT_TILES`` fallback otherwise).  Kernels with no tunable
blocking (``tunable=None``: the materializing dense oracle and the
``xla`` conv cells, where XLA picks the tiling) accept and ignore the
keyword; every other FUSED entry — including the dense-backend MXU
kernels of kernels/dense_fused.py — declares a ``TuningSpace``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Tuple

from repro.kernels.modes import QuantMode

__all__ = ["KernelSpec", "register", "lookup", "has", "available",
           "backends", "modes", "capability_table", "LAYOUT_GEMM",
           "LAYOUT_IM2COL"]

LAYOUT_GEMM = "gemm"              # A operand is an (m, k) matrix
LAYOUT_IM2COL = "im2col_fused"    # A operand is (B, H, W, Cin); kernel im2cols


@dataclasses.dataclass(frozen=True)
class KernelSpec:
    """One registered kernel + the capability metadata consumers need to
    pick (or enumerate) it without knowing its internals."""
    mode: QuantMode
    backend: str              # "pallas" | "xla" | "dense" | ...
    fused: bool               # epilogue included in the kernel/trace
    fn: Callable
    epilogue: str             # "in-kernel" | "scan-carry" | "none"
    compute: str              # "vpu-popcount" | "mxu-dense" | "mxu-int8" | ...
    description: str = ""
    # Autotuning descriptor (repro.tune.space.TuningSpace) — the set of
    # (block_m, block_n, block_kw, word_chunk) candidates the tuner may
    # measure for this kernel.  None means the kernel has no tunable
    # blocking (the materializing dense oracle and the xla conv cells,
    # where XLA picks the tiling — every other fused entry declares a
    # space).
    # Tunable kernels must accept a ``tiles=`` keyword (TileConfig).
    tunable: Optional[Any] = None
    layout: str = LAYOUT_GEMM  # "gemm" | "im2col_fused"
    # True when the kernel consumes extra QTensor payload keys beyond
    # the mode's bit planes (e.g. the indexed backend's pack-time
    # segment indices): dispatch then passes ``payload=qt.payload`` so
    # the kernel can zero-copy stored derived data, falling back to an
    # exact in-trace derivation when the keys are absent.
    payload_aware: bool = False

    @property
    def key(self) -> Tuple[QuantMode, str, bool, str]:
        return (self.mode, self.backend, self.fused, self.layout)


_REGISTRY: Dict[Tuple[QuantMode, str, bool, str], KernelSpec] = {}


def register(mode: QuantMode, backend: str, *, fused: bool,
             epilogue: str, compute: str, description: str = "",
             tunable: Optional[Any] = None, layout: str = LAYOUT_GEMM,
             payload_aware: bool = False):
    """Decorator: register ``fn`` as THE kernel for (mode, backend,
    fused, layout).  Re-registration overwrites (lets tests/backends
    shadow an entry)."""

    def deco(fn: Callable) -> Callable:
        spec = KernelSpec(mode=mode, backend=backend, fused=fused, fn=fn,
                          epilogue=epilogue, compute=compute,
                          description=description, tunable=tunable,
                          layout=layout, payload_aware=payload_aware)
        _REGISTRY[spec.key] = spec
        return fn

    return deco


def lookup(mode: QuantMode, backend: str, *, fused: bool,
           layout: str = LAYOUT_GEMM) -> KernelSpec:
    try:
        return _REGISTRY[(mode, backend, fused, layout)]
    except KeyError:
        have = sorted(f"{m.value}/{b}{'/fused' if f else ''}"
                      f"{'/' + lay if lay != LAYOUT_GEMM else ''}"
                      for (m, b, f, lay) in _REGISTRY)
        raise KeyError(
            f"no {'fused ' if fused else ''}kernel registered for "
            f"mode={mode.value} backend={backend!r} layout={layout!r}; "
            f"registered: {have}"
        ) from None


def has(mode: QuantMode, backend: str, *, fused: bool,
        layout: str = LAYOUT_GEMM) -> bool:
    return (mode, backend, fused, layout) in _REGISTRY


def available(mode: Optional[QuantMode] = None,
              backend: Optional[str] = None,
              fused: Optional[bool] = None,
              layout: Optional[str] = None) -> List[KernelSpec]:
    """All registered kernels matching the given filters, in a stable
    (mode, backend, fused, layout) order — what benchmarks and tests
    enumerate.  ``layout=None`` matches every layout; pass
    ``layout=LAYOUT_GEMM`` to enumerate only the matmul-shaped kernels."""
    out = [s for s in _REGISTRY.values()
           if (mode is None or s.mode == mode)
           and (backend is None or s.backend == backend)
           and (fused is None or s.fused == fused)
           and (layout is None or s.layout == layout)]
    return sorted(out, key=lambda s: (s.mode.value, s.backend, s.fused,
                                      s.layout))


def backends(mode: Optional[QuantMode] = None) -> List[str]:
    return sorted({s.backend for s in available(mode=mode)})


def modes(backend: Optional[str] = None) -> List[QuantMode]:
    seen = {s.mode for s in available(backend=backend)}
    return sorted(seen, key=lambda m: m.value)


def capability_table() -> str:
    """Human-readable mode x backend x layout x fused x tunable table —
    the quick triage view behind ``python -m repro.kernels.registry``."""
    header = (f"{'mode':>5s} {'backend':>8s} {'layout':>13s} {'fused':>6s} "
              f"{'epilogue':>11s} {'compute':>13s} {'tunable':>18s}  "
              f"description")
    lines = [header, "-" * len(header)]
    for s in available():
        if s.tunable is None:
            tun = "-"
        else:
            axes = (len(s.tunable.block_m), len(s.tunable.block_n),
                    len(s.tunable.block_kw), len(s.tunable.word_chunk))
            tun = f"{s.tunable.kind}({'x'.join(map(str, axes))})"
        lines.append(f"{s.mode.value:>5s} {s.backend:>8s} {s.layout:>13s} "
                     f"{str(s.fused).lower():>6s} {s.epilogue:>11s} "
                     f"{s.compute:>13s} {tun:>18s}  {s.description}")
    return "\n".join(lines)


def _main() -> int:
    # ``python -m repro.kernels.registry`` imports this module as
    # __main__; the populated table lives in the re-imported instance, so
    # enumerate through that (importing ops registers every kernel).
    import repro.kernels.ops  # noqa: F401  (side effect: registration)
    from repro.kernels import registry as populated

    print(populated.capability_table())
    n = len(populated.available())
    print(f"\n{n} kernels registered "
          f"({len(populated.modes())} modes x {len(populated.backends())} "
          f"backends; 'tunable' = TuningSpace kind(axis sizes))")
    return 0


if __name__ == "__main__":
    raise SystemExit(_main())
