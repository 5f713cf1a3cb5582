"""Profiler trace-annotation hook.

``annotate("prefill_chunk")`` wraps a host-side region in a
``jax.profiler.TraceAnnotation`` so device traces captured with
``jax.profiler.trace(...)`` line up with engine events.  Keyword
arguments become the span's stats (``annotate("sched/release",
uid=7)``), and ``set_metadata(**stats)`` adds stats known only once the
region ran.  When obs is disabled (or jax's profiler is unavailable) it
degrades to a null span — the serving loop never pays for it.

jax is imported lazily so ``repro.obs`` stays importable (and
stdlib-only) in tooling contexts that never touch the device.
"""

from __future__ import annotations

from .registry import obs_enabled

__all__ = ["annotate"]

_TRACE_CTX = None            # resolved on first enabled use


class _NullSpan:
    """What ``annotate`` returns while obs is off: a context manager
    that records nothing."""

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **stats) -> None:
        pass


_NULL = _NullSpan()


def _resolve():
    global _TRACE_CTX
    if _TRACE_CTX is None:
        try:
            from jax.profiler import TraceAnnotation
            _TRACE_CTX = TraceAnnotation
        except Exception:                       # pragma: no cover
            _TRACE_CTX = _NullSpan
    return _TRACE_CTX


def annotate(name: str, **stats):
    """Context manager naming a host region in jax profiler traces."""
    if not obs_enabled():
        return _NULL
    ctx = _resolve()
    if ctx is _NullSpan:                        # pragma: no cover
        return _NULL
    return ctx(name, **stats)
