"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train \
        --arch tinyllama-1.1b --smoke --steps 50 --batch 8 --seq 128

On this CPU container it runs smoke-scale configs on a (1, N) host mesh;
on a real cluster the same entry point runs the full config on the
production mesh (--production) after jax.distributed.initialize picks up
the pod topology from the environment.
"""

from __future__ import annotations

import argparse

import jax

from repro.configs import get_config, get_smoke
from repro.data import SyntheticLM
from repro.launch.cache import use_compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models.common import ShardLayout
from repro.optim.adamw import AdamWConfig
from repro.parallel import sharding
from repro.train import Trainer, TrainerConfig, TrainStepConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--production", action="store_true",
                    help="production 16x16 mesh (needs 256 devices)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatch", type=int, default=1)
    ap.add_argument("--quant", default=None,
                    help="quant policy: bf16|int8|int4|tnn|tbn|bnn")
    ap.add_argument("--int8-moments", action="store_true")
    ap.add_argument("--ef-compression", action="store_true")
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    use_compile_cache()

    over = {"quant_policy": args.quant} if args.quant else {}
    cfg = (get_smoke(args.arch, **over) if args.smoke
           else get_config(args.arch, **over))
    mesh = (make_production_mesh() if args.production else make_host_mesh())
    layout = ShardLayout(tp=dict(zip(mesh.axis_names,
                                     mesh.devices.shape)).get("model", 1))

    tcfg = TrainStepConfig(
        optimizer=AdamWConfig(
            lr=args.lr, total_steps=args.steps,
            warmup_steps=max(1, args.steps // 10),
            moments_dtype="int8" if args.int8_moments else "f32"),
        microbatch=args.microbatch,
        ef_compression=args.ef_compression,
    )
    source = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=args.seq,
                         global_batch=args.batch, seed=args.seed)
    tr = TrainerConfig(steps=args.steps, seed=args.seed,
                       checkpoint_dir=args.checkpoint_dir,
                       checkpoint_every=max(10, args.steps // 4))

    with sharding.use_mesh(mesh, sharding.TRAIN_RULES):
        trainer = Trainer(cfg, layout, tcfg, tr, source,
                          num_hosts=jax.process_count(),
                          host_id=jax.process_index())
        result = trainer.run()
    print(f"[launch.train] done at step {result.final_step}; "
          f"loss {result.losses[0]:.3f} -> {result.losses[-1]:.3f}")


if __name__ == "__main__":
    main()
