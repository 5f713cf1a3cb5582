"""The measuring half of the autotuner: run each candidate blocking on
the live device, keep the median-of-k wall time, return a
:class:`~repro.tune.cache.Plan`.

Determinism contract (the "shape-stable" acceptance bar):

* operands are synthesized from a fixed PRNG seed, so every run measures
  the same bits;
* candidate order is deterministic (``TuningSpace.candidates``: default
  first, then the axis product) and the winner is the argmin of median
  times with ties resolving to the *earlier* candidate;
* the persisted JSON carries only the decision (tiles + key), never the
  raw timings, so a re-run that reaches the same decision re-saves a
  byte-identical file — and a re-run against a warm cache measures
  nothing at all.

The tuner times the *registered kernel entry* (``KernelSpec.fn`` with an
explicit ``tiles=`` override), i.e. exactly the code path ``ops.qmm``
dispatches to, on the same device and with the same ``interpret``
setting — not a proxy model.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from repro.kernels import registry
from repro.kernels.modes import QuantMode
from repro.tune import cache as plan_cache
from repro.tune.space import TuningSpace
from repro import obs

# NOTE: repro.kernels.ops / repro.core are imported lazily inside the
# functions below — ops imports this package's siblings at module scope,
# and repro.core's own __init__ re-enters ops; a top-level import here
# would close that cycle during interpreter start-up.

__all__ = ["ConvProblem", "tune_one", "ensure_plan", "tune_shapes",
           "collect_problems", "measure"]

# ensure_plan telemetry (process registry; no-ops when REPRO_OBS=off):
# the "on_first_use" hot path must stay a dict lookup, so the hit arm
# records ONE counter bump and nothing else.
_ENSURE_CTR = obs.get_registry().counter(
    "repro_tune_ensure_total",
    "ensure_plan outcomes by result (hit | measured)",
    labels=("result",))
_MEASURE_HIST = obs.get_registry().histogram(
    "repro_tune_measure_seconds",
    "on-device candidate measurement latency per ensure_plan")


@dataclasses.dataclass(frozen=True)
class ConvProblem:
    """One fused-im2col conv tuning problem (registry layout
    ``im2col_fused``): the input tensor extents plus the conv geometry.
    Unlike a GeMM problem, the implicit (m, n, k) alone does not pin the
    kernel's gather schedule, so plans for these key on an extra
    ``geom`` tag (see ``cache.plan_key``)."""
    batch: int
    height: int
    width: int
    cin: int
    cout: int
    kernel_h: int
    kernel_w: int
    stride: int = 1
    padding: str = "SAME"

    @classmethod
    def from_input(cls, x_shape, geometry, stride: int = 1,
                   padding: str = "SAME") -> "ConvProblem":
        b, h, w, _ = x_shape
        kh, kw, cin, cout = geometry
        return cls(batch=int(b), height=int(h), width=int(w), cin=int(cin),
                   cout=int(cout), kernel_h=int(kh), kernel_w=int(kw),
                   stride=int(stride), padding=str(padding))

    @property
    def geometry(self) -> Tuple[int, int, int, int]:
        return (self.kernel_h, self.kernel_w, self.cin, self.cout)

    @property
    def x_shape(self) -> Tuple[int, int, int, int]:
        return (self.batch, self.height, self.width, self.cin)

    def dims(self) -> Tuple[int, int, int, str]:
        """(m, n, k, geom_tag) of the implicit im2col GeMM."""
        from repro.kernels import conv_fused

        return conv_fused.conv_problem_dims(self.x_shape, self.geometry,
                                            self.stride, self.padding)

    @property
    def kw_words(self) -> int:
        """True reduction word count of the fused conv kernels: each
        patch position packs word-aligned, so this exceeds
        ``words_for(k)`` whenever ``cin % 32 != 0``."""
        return self.kernel_h * self.kernel_w * (-(-self.cin // 32))


def measure(call, *, warmup: int = 1, reps: int = 3) -> float:
    """Median wall time of ``call()`` (which must return a JAX array).
    The warmup iterations absorb compilation; reps are timed
    individually so one scheduler hiccup cannot skew the median."""
    for _ in range(max(1, warmup)):
        call().block_until_ready()
    ts = []
    for _ in range(max(1, reps)):
        t0 = time.perf_counter()
        call().block_until_ready()
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def _make_problem(mode: QuantMode, m: int, n: int, k: int, seed: int):
    """Fixed-seed packed operands for one (mode, m, n, k) problem:
    (a_planes, b_planes, row_scale, col_scale)."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops

    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.normal(k1, (m, k), jnp.float32)
    w = jax.random.normal(k2, (k, n), jnp.float32)
    xa = ops.quantize_activations(x, mode)
    qt = ops.pack_weights(w, mode)
    a_planes = tuple(xa[key] for key in ops._A_KEYS[mode])
    b_planes = ops._b_planes(qt, mode)
    row = ops._as_row_scale(xa["scale"], m)
    col = ops._as_col_vec(qt.scale, n)
    return a_planes, b_planes, row, col


def _make_conv_problem(mode: QuantMode, conv: ConvProblem, seed: int):
    """Fixed-seed operands for one fused-im2col conv problem:
    (x, b_planes, stats, col_scale)."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import conv_fused, ops

    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.normal(k1, conv.x_shape, jnp.float32)
    kh, kw, cin, cout = conv.geometry
    w = jax.random.normal(k2, (kh * kw * cin, cout), jnp.float32)
    qt = ops.QTensor.from_dense(w, mode, geometry=conv.geometry)
    stats = conv_fused.conv_act_stats(x, mode, kh, kw, conv.stride,
                                      conv.padding)
    col = ops._as_col_vec(qt.scale, cout)
    # conv kernels consume the per-patch-position weight layout (the
    # same planes ops._qconv_jit dispatches with)
    return x, conv_fused.conv_weight_planes(qt), stats, col


def tune_one(mode: QuantMode, backend: str, *, fused: bool = True,
             m: Optional[int] = None, n: Optional[int] = None,
             k: Optional[int] = None,
             space: Optional[TuningSpace] = None,
             reps: int = 3, warmup: int = 1, seed: int = 0,
             interpret: bool | None = None,
             conv: Optional[ConvProblem] = None,
             ) -> Tuple[plan_cache.Plan, Dict]:
    """Measure every candidate blocking for one problem and return the
    winning :class:`Plan` plus a per-candidate timing report.

    GeMM problems are measured at their **m-bucket** (the plan's cache
    granularity), so every shape that later resolves to this plan was
    represented by the measurement.  Passing ``conv`` instead tunes the
    fused-im2col conv kernel for that geometry (layout "im2col_fused" in
    the registry; ``m``/``n``/``k`` are derived and must not be given) —
    conv problems measure at their exact input extents, since the
    geometry fixes the patch count.
    """
    layout = registry.LAYOUT_GEMM
    geom = None
    if conv is not None:
        if not (m is None and n is None and k is None):
            raise ValueError("pass either conv= or explicit m/n/k, not both")
        m, n, k, geom = conv.dims()
        layout = registry.LAYOUT_IM2COL
    if m is None or n is None or k is None:
        raise ValueError("tune_one needs m, n, k (or a conv problem)")
    spec = registry.lookup(mode, backend, fused=fused, layout=layout)
    space = space if space is not None else spec.tunable
    mb = plan_cache.bucket_m(m)
    if space is None:
        # untunable kernel: the default plan IS the decision
        plan = plan_cache.default_plan(mode, backend, fused, m, n, k,
                                       layout=layout, geom=geom)
        return plan, {"candidates": [], "best_index": -1,
                      "untunable": True}
    default = plan_cache.default_plan(mode, backend, fused, m, n, k,
                                      layout=layout, geom=geom).tiles
    cands = space.candidates(m if conv is not None else mb, n, k,
                             default=default,
                             kw=None if conv is None else conv.kw_words)

    import jax

    if conv is not None:
        x, b_pl, stats, col = _make_conv_problem(mode, conv, seed)
    else:
        a_pl, b_pl, row, col = _make_problem(mode, mb, n, k, seed)

    times: List[float] = []
    for tc in cands:
        # Measure the jitted kernel — the form ops.qmm/qconv dispatches
        # (its whole pipeline is one jit trace); timing eager dispatch
        # would rank candidates by Python overhead instead of kernel
        # time.
        if conv is not None:
            jfn = jax.jit(lambda x_, b, s, c, tc=tc: spec.fn(
                x_, b, conv.geometry, conv.stride, conv.padding, s, c,
                None, interpret=interpret, tiles=tc))
            call = lambda jfn=jfn: jfn(x, b_pl, stats, col)
        elif fused:
            jfn = jax.jit(lambda a, b, r, c, tc=tc: spec.fn(
                a, b, k, r, c, None, interpret=interpret, tiles=tc))
            call = lambda jfn=jfn: jfn(a_pl, b_pl, row, col)
        else:
            jfn = jax.jit(lambda a, b, tc=tc: spec.fn(
                a, b, k, interpret=interpret, tiles=tc))
            call = lambda jfn=jfn: jfn(a_pl, b_pl)
        times.append(measure(call, warmup=warmup, reps=reps))

    best = int(np.argmin(times))          # ties -> earliest candidate
    plan = plan_cache.Plan(
        mode=mode, backend=backend, fused=fused,
        device_kind=plan_cache.device_kind(), m_bucket=mb, n=n, k=k,
        tiles=cands[best], source="tuned", layout=layout, geom=geom)
    report = {
        "candidates": [{"tiles": tc.to_json(), "median_s": t}
                       for tc, t in zip(cands, times)],
        "best_index": best,
        "default_s": times[0],            # candidate 0 is the default
        "best_s": times[best],
    }
    return plan, report


def ensure_plan(mode: QuantMode, backend: str, *, fused: bool = True,
                m: Optional[int] = None, n: Optional[int] = None,
                k: Optional[int] = None,
                reps: int = 3, warmup: int = 1, seed: int = 0,
                interpret: bool | None = None, save: bool = True,
                reports: Optional[Dict[str, Dict]] = None,
                conv: Optional[ConvProblem] = None,
                ) -> Tuple[plan_cache.Plan, bool]:
    """Cache-or-measure: returns ``(plan, measured)``.  A warm cache is a
    pure dict lookup — this is what ``ops.qmm``/``ops.qconv`` call per
    invocation under the "on_first_use" policy, so the hit path must
    stay cheap.  ``conv`` selects the fused-im2col conv problem form
    (m/n/k derived from the geometry).

    ``reports`` (optional dict) collects the per-candidate timing table
    of every measurement actually performed, keyed by plan key — the
    single-pass source for ``python -m repro.tune --report`` (re-running
    the sweep just for the report could crown a different winner on
    timing noise and contradict the persisted plan)."""
    layout = registry.LAYOUT_GEMM
    geom = None
    if conv is not None:
        m, n, k, geom = conv.dims()
        layout = registry.LAYOUT_IM2COL
    if m is None or n is None or k is None:
        raise ValueError("ensure_plan needs m, n, k (or a conv= problem)")
    # Hard-failure containment (docs/resilience.md): past argument
    # validation, NOTHING in the cache-or-measure path may propagate
    # into kernel dispatch — a broken cache file, a failed measurement,
    # or a failed save all resolve to the DEFAULT_TILES plan.
    try:
        cache = plan_cache.get_cache()
        key = plan_cache.plan_key(mode, backend, fused,
                                  plan_cache.device_kind(),
                                  plan_cache.bucket_m(m), n, k,
                                  layout=layout, geom=geom)
        hit = cache.get(key)
        if hit is not None:
            _ENSURE_CTR.inc(result="hit")
            return hit, False
        _ENSURE_CTR.inc(result="measured")
        with _MEASURE_HIST.time():
            if conv is not None:
                plan, report = tune_one(mode, backend, fused=fused,
                                        conv=conv, reps=reps,
                                        warmup=warmup, seed=seed,
                                        interpret=interpret)
            else:
                plan, report = tune_one(mode, backend, fused=fused, m=m,
                                        n=n, k=k, reps=reps,
                                        warmup=warmup, seed=seed,
                                        interpret=interpret)
        if reports is not None:
            reports[plan.key] = report
        cache.put(plan)
    except Exception as e:
        plan_cache.contained("ensure_plan", e)
        return plan_cache.plan_for(mode, backend, fused=fused, m=m, n=n,
                                   k=k, layout=layout, geom=geom), False
    if save:
        try:
            cache.save()
        except Exception as e:
            # The tuned plan is live in memory either way; a failed
            # persist must not fail the dispatch that triggered tuning.
            plan_cache.contained("save", e)
    return plan, True


def tune_shapes(shapes: Iterable[Tuple[int, int, int]],
                modes: Sequence[QuantMode],
                backends: Sequence[str], *,
                fused: bool = True, reps: int = 3, warmup: int = 1,
                seed: int = 0, interpret: bool | None = None,
                verbose: bool = False,
                conv_problems: Sequence[ConvProblem] = (),
                ) -> Tuple[List[plan_cache.Plan], Dict[str, int],
                           Dict[str, Dict]]:
    """Offline sweep: ensure a plan for every (shape x mode x backend)
    that has a registered tunable kernel — GeMM shapes AND, optionally,
    fused-im2col conv geometries.  Returns ``(plans, stats, reports)``:
    ``{"measured": .., "cached": ..}`` stats (the CI smoke gate asserts
    a second run reports measured == 0) and the per-candidate timing
    tables of the entries measured in THIS run."""
    plans: List[plan_cache.Plan] = []
    stats = {"measured": 0, "cached": 0, "skipped": 0}
    reports: Dict[str, Dict] = {}

    def _one(mode, backend, layout, **kw):
        try:
            spec = registry.lookup(mode, backend, fused=fused,
                                   layout=layout)
        except KeyError:
            stats["skipped"] += 1
            return
        if spec.tunable is None:
            stats["skipped"] += 1
            return
        plan, measured = ensure_plan(
            mode, backend, fused=fused, reps=reps, warmup=warmup,
            seed=seed, interpret=interpret, save=False, reports=reports,
            **kw)
        stats["measured" if measured else "cached"] += 1
        plans.append(plan)
        if verbose:
            src = "measured" if measured else "cache-hit"
            print(f"  {plan.key:<46s} -> {plan.tiles.kernel_kwargs()}"
                  f"  [{src}]")

    for (m, n, k) in shapes:
        for mode in modes:
            for backend in backends:
                _one(mode, backend, registry.LAYOUT_GEMM, m=m, n=n, k=k)
    for prob in conv_problems:
        for mode in modes:
            for backend in backends:
                _one(mode, backend, registry.LAYOUT_IM2COL, conv=prob)
    cache = plan_cache.get_cache()
    try:
        cache.save()
    except Exception as e:
        # Sweep results stay live in the in-memory cache; a failed
        # persist is contained (the sweep itself succeeded).
        plan_cache.contained("save", e)
    return plans, stats, reports


def collect_problems(params) -> List[Tuple]:
    """All distinct packed-weight problems in a parameter tree — what
    the serving engine tunes at build time.  Each entry is ``(mode, k,
    n, geometry)`` with ``geometry=None`` for plain GeMM weights and the
    (kh, kw, cin, cout) aux for conv-packed QTensors (those tune through
    the fused-im2col kernels against caller-supplied input extents).
    Stacked (scanned / expert) QTensors contribute their logical 2-D
    shape."""
    import jax

    from repro.kernels.qtensor import QTensor

    seen = []
    for leaf in jax.tree.leaves(
            params, is_leaf=lambda x: isinstance(x, QTensor)):
        if isinstance(leaf, QTensor) and leaf.is_lowbit:
            prob = (leaf.mode, leaf.k_valid, leaf.out_features,
                    leaf.geometry)
            if prob not in seen:
                seen.append(prob)
    return seen
