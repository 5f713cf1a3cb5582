"""Compiled text of the programs a cell's window ran, for the join of
device ops to named scopes (``scopes.py``).

Each program is lowered again from its configuration with abstract
arguments of the shapes the runner gave it (the runner's own functions,
no arrays) and compiled twice.  The persistent compilation cache leaves
metadata out of its key, so the first compile gives the program as the
run loaded or compiled it: the instruction names the trace shows, but,
where the cache kept it from another version of the code, that
version's ``op_name``s.  The second keys the cache with metadata and so
names this code's scopes; named scopes change instruction names and
nothing else, so ``scopes.module_scopes`` matches the two instruction by
instruction, and ``scopes.ScopedOps`` checks the result against the
trace, op by op.  Called after the window and outside ``setup_s``, with
the compile counter disarmed; each program compiles once a process.
"""

from __future__ import annotations

import functools
import json
from typing import Any, Callable, Dict, List, Tuple

from . import counts

_TEXTS: Dict[str, Tuple[str, str]] = {}


def _texts(key: str, make: Callable[[], Tuple[Any, tuple]]
           ) -> Tuple[str, str]:
    """(as the run ran it, with this code's metadata) of the program
    ``make() -> (jitted, abstract args)``; each call of ``make`` builds
    a new function, so the second compile is not served from memory."""
    import jax

    if key not in _TEXTS:
        jitted, args = make()
        ran = jitted.lower(*args).compile().as_text()
        flag = "jax_compilation_cache_include_metadata_in_key"
        was = getattr(jax.config, flag)
        jax.config.update(flag, True)
        try:
            jitted, args = make()
            own = jitted.lower(*args).compile().as_text()
        finally:
            jax.config.update(flag, was)
        _TEXTS[key] = (ran, own)
    return _TEXTS[key]


def lm_serve_step_texts(cfg: Dict[str, Any]) -> Tuple[str, str]:
    """The engine's decode step (``jit_serve_step``) as ``lm.run``
    builds and calls it."""
    return _texts("serve_step " + json.dumps(cfg, sort_keys=True),
                  functools.partial(_serve_step, cfg))


def _serve_step(cfg):
    import jax
    import jax.numpy as jnp
    from repro.models import model as model_mod
    from repro.models.common import ShardLayout
    from repro.models.kvcache import init_caches
    from repro.models.packing import pack_lm_params
    from repro.serving import SamplerConfig, ServeConfig
    from repro.serving.engine import make_serve_step

    from . import lm, weights

    mcfg = lm.model_config(cfg)
    layout = ShardLayout(tp=1)
    serve = cfg["serve"]
    b, n = serve["num_slots"], serve["max_len"]
    scfg = ServeConfig(num_slots=b, max_len=n, page_size=serve["page_size"],
                       prefill_chunk=serve["prefill_chunk"], eos_id=-1,
                       pack_params=True, autotune="off",
                       sampler=SamplerConfig(temperature=0.0))
    key = jax.random.PRNGKey(0)
    shapes = jax.eval_shape(functools.partial(
        model_mod.init_lm, cfg=mcfg, layout=layout, dtype=jnp.bfloat16), key)
    params = jax.eval_shape(
        lambda k: pack_lm_params(weights.make_tree(k, shapes), mcfg), key)
    caches = jax.eval_shape(lambda: init_caches(
        mcfg, layout, b, n, page_size=scfg.page_size,
        prefill_chunk=scfg.prefill_chunk))
    sub = jax.eval_shape(lambda: jax.random.split(key)[1])
    args = (params, caches, jax.ShapeDtypeStruct((b, 1), jnp.int32),
            jax.ShapeDtypeStruct((b,), jnp.int32), sub)
    return jax.jit(make_serve_step(mcfg, layout, scfg)), args


def cnn_forward_texts(cfg: Dict[str, Any], batch: int) -> Tuple[str, str]:
    """The harness's jitted forward (``jit_forward``) at ``batch``."""
    return _texts(f"forward {batch} " + json.dumps(cfg, sort_keys=True),
                  functools.partial(_forward, cfg, batch))


def _forward(cfg, batch):
    import jax
    import jax.numpy as jnp

    from . import cnn

    params = jax.eval_shape(cnn.param_maker(cfg), jax.random.PRNGKey(0))
    images = jax.ShapeDtypeStruct(
        (batch, cfg["img_size"], cfg["img_size"], cfg["c_in"]), jnp.float32)
    return cnn.make_forward(cfg), (images, params)


def cnn_batch(ctx) -> int:
    """Images a batch of a CNN cell's window (from its model ops)."""
    return round(ctx.model_ops / ctx.batches / counts.cnn_image_ops(ctx.cfg))


def qconv_least(ctx) -> List[Dict[str, Any]]:
    """Each low-bit conv of a CNN cell's batch (``ops.qconv``): the least
    time the chip could take for it, and whether operations or bytes set
    it."""
    b, out = cnn_batch(ctx), []
    for i, L in enumerate(counts.cnn_layers(ctx.cfg)):
        if L["kind"] != "conv" or L["mode"] not in counts.PLANES:
            continue
        ops = counts.qconv_ops(b, L["oh"], L["ow"], L["k"], L["k"], L["cin"],
                               L["cout"])
        nbytes = counts.qconv_bytes(b, L["h"], L["w"], L["oh"], L["ow"],
                                    L["k"], L["k"], L["cin"], L["cout"],
                                    L["mode"])
        t_ops = ops / ctx.peaks["int8_ops"]
        t_bytes = nbytes / ctx.peaks["hbm_bytes_per_s"]
        out.append({"layer": i, "mode": L["mode"],
                    "least_s": counts.least_time_s(ops, nbytes, ctx.peaks),
                    "bound": "ops" if t_ops >= t_bytes else "bytes"})
    return out
