"""Production training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch llama3_2_1b \
        --steps 1000 --ckpt /data/ckpt --mesh 16x16

On a real fleet each host runs this after jax.distributed.initialize();
here it sizes the mesh to whatever devices exist (elastic.best_mesh_for),
shards params/optimizer with the production rules, and runs the
fault-tolerant loop (ENEC checkpoints, straggler watchdog, resume).
"""
from __future__ import annotations

import argparse
import dataclasses
from pathlib import Path

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint.ckpt import CheckpointManager
from repro.configs import get_config, get_smoke_config
from repro.data.pipeline import DataConfig
from repro.launch.compile_cache import use_compile_cache
from repro.models import build_model
from repro.optim import adamw
from repro.runtime import elastic, sharding
from repro.runtime.steps import build_train_step
from repro.runtime.train_loop import TrainLoopConfig, run


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3_2_1b")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt", default="/tmp/repro_ckpt")
    ap.add_argument("--mesh", default=None, help="e.g. 16x16 (default: auto)")
    args = ap.parse_args()
    use_compile_cache()

    cfg = (get_smoke_config if args.smoke else get_config)(args.arch)
    cfg = dataclasses.replace(cfg, scan_layers=True)
    if args.mesh:
        from repro.launch.mesh import make_mesh
        shape = tuple(int(v) for v in args.mesh.split("x"))
        mesh = make_mesh(shape, ("data", "model")[: len(shape)])
    else:
        mesh = elastic.best_mesh_for(cfg)
    print(f"[launch.train] {cfg.name} on mesh {dict(mesh.shape)}")

    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    opt_state = adamw.init(params)
    pspecs = sharding.param_pspecs(params, mesh, mode="train")

    def named(t):
        return jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                            is_leaf=lambda x: isinstance(x, P))

    params = jax.device_put(params, named(pspecs))
    opt_specs = adamw.AdamWState(step=P(), m=pspecs, v=pspecs)
    opt_state = jax.device_put(opt_state, named(opt_specs))

    opt_cfg = adamw.AdamWConfig(
        lr=args.lr, schedule=adamw.warmup_cosine(20, args.steps))
    step_fn = jax.jit(build_train_step(model, opt_cfg),
                      donate_argnums=(0, 1))
    data_cfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                          global_batch=args.global_batch)
    out = run(model, opt_cfg, data_cfg,
              TrainLoopConfig(total_steps=args.steps, ckpt_every=50,
                              log_every=10),
              ckpt=CheckpointManager(Path(args.ckpt)),
              train_step=step_fn, params=params, opt_state=opt_state,
              on_metrics=lambda r: print(f"  step {r['step']} "
                                         f"loss {r['loss']:.4f}"))
    print(f"[launch.train] done: {out['history'][-1]}")


if __name__ == "__main__":
    main()
