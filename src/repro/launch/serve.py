"""Serving launcher: batched requests through the continuous-batching
engine.

    PYTHONPATH=src python -m repro.launch.serve \
        --arch tinyllama-1.1b --smoke --requests 16 --new-tokens 24
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config, get_smoke
from repro.launch.cache import use_compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models import model as model_mod
from repro.models.common import ShardLayout
from repro.parallel import sharding
from repro.serving import Engine, Request, SamplerConfig, ServeConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--production", action="store_true")
    ap.add_argument("--quant", default=None)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--new-tokens", type=int, default=24)
    ap.add_argument("--temperature", type=float, default=0.8)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    use_compile_cache()

    over = {"quant_policy": args.quant} if args.quant else {}
    cfg = (get_smoke(args.arch, **over) if args.smoke
           else get_config(args.arch, **over))
    mesh = (make_production_mesh() if args.production else make_host_mesh())
    layout = ShardLayout(tp=dict(zip(mesh.axis_names,
                                     mesh.devices.shape)).get("model", 1))

    scfg = ServeConfig(num_slots=args.slots, max_len=args.max_len,
                       prefill_bucket=32,
                       sampler=SamplerConfig(temperature=args.temperature))

    with sharding.use_mesh(mesh, sharding.SERVE_RULES):
        params = model_mod.init_lm(jax.random.PRNGKey(args.seed), cfg, layout)
        engine = Engine(params, cfg, layout, scfg, seed=args.seed)
        rng = np.random.default_rng(args.seed)
        t0 = time.time()
        for uid in range(args.requests):
            plen = int(rng.integers(4, 24))
            prompt = rng.integers(0, cfg.vocab_size, plen).astype(np.int32)
            engine.submit(Request(uid=uid, prompt=prompt,
                                  max_new_tokens=args.new_tokens))
        results = engine.run()
        dt = time.time() - t0

    total_tokens = sum(len(r.tokens) for r in results.values())
    print(f"[launch.serve] {len(results)}/{args.requests} requests, "
          f"{total_tokens} tokens in {dt:.1f}s "
          f"({total_tokens/max(dt,1e-9):.1f} tok/s)")
    for uid in sorted(results)[:4]:
        print(f"  req {uid}: {results[uid].tokens[:12]} ...")


if __name__ == "__main__":
    main()
