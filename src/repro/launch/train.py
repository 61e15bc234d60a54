"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b --smoke \
        --steps 100 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt

On this CPU container use ``--smoke`` (reduced config).  On a real pod the
same entry point runs the full config on the production mesh (--mesh pod).
Resume is automatic if the checkpoint directory has state.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import numpy as np

from ..checkpoint import CheckpointManager
from ..configs import ARCH_IDS, get_config, smoke_config
from ..data.pipeline import SyntheticLMData
from ..distributed.axes import logical_axes
from ..distributed.sharding import batch_spec, shardings_of, state_specs
from ..launch.compile_cache import enable_compile_cache
from ..launch.mesh import make_host_mesh, make_production_mesh
from ..optim.adamw import AdamWConfig
from ..train.loop import TrainLoop
from ..train.step import init_train_state, make_train_step


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b", choices=ARCH_IDS)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--log", default=None)
    ap.add_argument("--mesh", default="host", choices=["host", "pod",
                                                       "multipod"])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    cfg = dataclasses.replace(cfg, grad_accum=1)
    opt_cfg = AdamWConfig(lr=args.lr, warmup_steps=max(args.steps // 10, 1))
    if args.mesh == "host":
        mesh = make_host_mesh(1, 1)
    else:
        mesh = make_production_mesh(multi_pod=args.mesh == "multipod")

    data = SyntheticLMData(cfg, args.batch, args.seq, seed=args.seed)
    ckpt = CheckpointManager(args.ckpt_dir)

    with mesh, logical_axes(mesh, n_experts=cfg.n_experts):
        state = init_train_state(cfg, opt_cfg, jax.random.key(args.seed))
        st_specs = state_specs(cfg, jax.eval_shape(lambda: state), mesh)
        st_sh = shardings_of(st_specs, mesh)
        restored, start = ckpt.restore_latest(state, st_sh)
        if restored is not None:
            state, start_step = restored, start
            print(f"[train] resumed from step {start_step}")
        else:
            state = jax.device_put(state, st_sh)
            start_step = 0
        step_fn = make_train_step(cfg, opt_cfg)
        b0 = jax.tree.map(lambda x: jax.numpy.asarray(x), data.batch_at(0))
        b_sh = shardings_of(batch_spec(cfg, jax.eval_shape(lambda: b0),
                                       mesh), mesh)
        jitted = jax.jit(step_fn, in_shardings=(st_sh, b_sh),
                         donate_argnums=(0,))

        def step(state, batch):
            batch = jax.device_put(
                jax.tree.map(jax.numpy.asarray, batch), b_sh)
            return jitted(state, batch)

        loop = TrainLoop(step, data.batch_at, ckpt, log_path=args.log,
                         ckpt_every=args.ckpt_every)
        t0 = time.time()
        state, end_step, losses = loop.run(state, start_step, args.steps)
        dt = time.time() - t0
    n = end_step - start_step
    print(f"[train] {cfg.name}: steps {start_step}->{end_step} "
          f"({dt/max(n,1):.2f}s/step)  loss {losses[0]:.4f} -> "
          f"{losses[-1]:.4f}  (min {losses.min():.4f})  "
          f"stragglers={loop.monitor.slow_steps}")


if __name__ == "__main__":
    main()
