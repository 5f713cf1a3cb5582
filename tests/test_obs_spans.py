"""Profiler spans of the scheduler's tick and the named scopes of the
low-bit kernels and the paged cache.

* With obs on, a paged engine's ticks under ``jax.profiler`` carry an
  ``engine/tick`` span holding every ``sched/*`` span, with their stats;
  with obs off, none of them.
* The spans change nothing the engine serves: the same requests give
  the same tokens with obs on and off.
* ``ops.qmm``, ``ops.qconv`` and ``paged_kvcache.page_view`` name their
  ops in the compiled program (``qmm[<mode>]``, ``qconv[<mode>]``,
  ``kv_page_view``).
"""

import glob
import os
import re
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs

SCHED = {"sched/expire", "sched/admit", "sched/release", "sched/page_sync",
         "sched/inputs", "sched/prefill_wait", "sched/logits_pull",
         "sched/numeric_guard", "sched/token_wait", "sched/emit"}


@pytest.fixture(scope="module")
def smoke():
    from repro.configs import get_smoke
    from repro.models import model as model_mod
    from repro.models.common import ShardLayout

    cfg = get_smoke("tinyllama-1.1b")
    layout = ShardLayout(tp=1)
    params = model_mod.init_lm(jax.random.PRNGKey(1234), cfg, layout)
    return cfg, layout, params


@pytest.fixture()
def obs_switch():
    was = obs.obs_enabled()
    yield obs.set_enabled
    obs.set_enabled(was)


def _serve(smoke, n=6):
    """Tokens of ``n`` overlapping requests on a 4-slot paged engine."""
    from repro.serving import Engine, Request, SamplerConfig, ServeConfig

    cfg, layout, params = smoke
    eng = Engine(params, cfg.with_(kv_cache_dtype="tnn2"), layout,
                 ServeConfig(num_slots=4, max_len=64, page_size=8,
                             prefill_chunk=8,
                             sampler=SamplerConfig(temperature=0.0)), seed=0)
    rng = np.random.default_rng(7)
    for uid in range(n):
        eng.submit(Request(uid=uid, prompt=rng.integers(
            0, cfg.vocab_size, 6 + 5 * uid), max_new_tokens=4 + uid))
    results = eng.run()
    eng.close()
    return {u: list(r.tokens) for u, r in results.items()}


def _host_spans(trace_dir):
    """(name, start, end, stats) of every host event in the trace."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    out = []
    with warnings.catch_warnings():
        # jaxlib's event_stats type warns on every iteration
        warnings.simplefilter("ignore", DeprecationWarning)
        for pl in ProfileData.from_file(path).planes:
            if pl.name.startswith("/device:"):
                continue
            for ln in pl.lines:
                for e in ln.events:
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return out


def _traced_tokens(smoke, tmp_path):
    # as the benchmark's capture (bench/benchkit/trace.py): annotations
    # only, no Python call stacks
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        tokens = _serve(smoke)
    return tokens, _host_spans(str(tmp_path))


def test_tick_spans_nest_in_engine_tick_with_stats(smoke, obs_switch,
                                                   tmp_path):
    obs_switch(True)
    tokens, events = _traced_tokens(smoke, tmp_path)
    ticks = [e for e in events if e[0] == "engine/tick"]
    sched = [e for e in events if e[0].startswith("sched/")]
    assert ticks and {e[0] for e in sched} == SCHED
    assert [int(t[3]["tick"]) for t in ticks] == list(
        range(1, len(ticks) + 1))
    for name, a, b, _ in sched:
        assert any(t[1] <= a and b <= t[2] for t in ticks), name
    # spans of one request carry its uid
    released = {int(e[3]["uid"]) for e in sched if e[0] == "sched/release"}
    assert released == set(tokens)
    admitted = [int(u) for e in sched if e[0] == "sched/admit"
                and "uids" in e[3] for u in re.findall(r"\d+", e[3]["uids"])]
    assert sorted(admitted) == sorted(tokens)
    pulls = [e[3] for e in sched if e[0] == "sched/logits_pull"]
    assert pulls and all(int(p["bytes"]) > 0 and "uids" in p for p in pulls)
    # the tick waits for a chunk only when it completes a prompt
    waits = [e for e in sched if e[0] == "sched/prefill_wait"]
    assert len(waits) == len(pulls)


def test_no_tick_spans_with_obs_off(smoke, obs_switch, tmp_path):
    obs_switch(False)
    _, events = _traced_tokens(smoke, tmp_path)
    names = {e[0] for e in events}
    assert "engine/tick" not in names and not names & SCHED


def test_tokens_are_the_same_with_obs_on_and_off(smoke, obs_switch):
    obs_switch(True)
    on = _serve(smoke)
    obs_switch(False)
    off = _serve(smoke)
    assert on == off and all(len(t) > 1 for t in on.values())


def test_span_stats_can_come_after_the_region(obs_switch):
    obs_switch(False)
    with obs.annotate("sched/admit") as span:
        span.set_metadata(uids=[1, 2])          # a no-op with obs off
    obs_switch(True)
    with obs.annotate("sched/admit") as span:
        span.set_metadata(uids=[1, 2])


def _op_names(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("mode", ["tnn", "tbn", "bnn"])
def test_qmm_names_its_ops(mode):
    from repro.kernels import ops
    from repro.kernels.modes import QuantMode
    from repro.kernels.qtensor import QTensor

    w = jax.random.normal(jax.random.PRNGKey(0), (64, 32))
    qt = QTensor.from_dense(w, QuantMode(mode))
    text = _op_names(lambda x: ops.qmm(x, qt), jnp.ones((4, 64)))
    assert f'/qmm[{mode}]/' in text


def test_qconv_names_its_ops():
    from repro.core.conv import pack_conv_filters
    from repro.kernels import ops
    from repro.kernels.modes import QuantMode

    w = jax.random.normal(jax.random.PRNGKey(0), (3, 3, 32, 16))
    qt = pack_conv_filters(w, QuantMode.TNN)
    text = _op_names(lambda x: ops.qconv(x, qt, backend="xla"),
                     jnp.ones((2, 8, 8, 32)))
    assert '/qconv[tnn]/' in text


def test_page_view_names_its_ops(smoke):
    from repro.models import paged_kvcache as paged
    from repro.models.common import ShardLayout

    cfg, layout, _ = smoke
    entry = jax.tree.map(lambda a: a[0], paged.init_paged_caches(
        cfg, ShardLayout(tp=1), 2, 32, page_size=8)[0])
    text = _op_names(
        lambda e: paged.page_view(e, cfg.num_kv_heads, cfg.head_dim_), entry)
    assert "/kv_page_view/" in text
