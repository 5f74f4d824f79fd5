"""End-to-end training driver.

Composes the full substrate: config registry -> data pipeline -> sharded
train step (remat/grad-accum/compression) -> async checkpointing ->
watchdog/straggler monitoring -> crash-loop restart.  On this CPU
container use ``--reduced``; on a real pod, point ``--mesh`` at the
production topology (the dry-run proves every cell lowers there).

  PYTHONPATH=src python -m repro.launch.train --arch internlm2_1_8b \
      --reduced --steps 200 --seq-len 128 --global-batch 8 \
      --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.checkpoint.checkpointer import Checkpointer, latest_step
from repro.configs import get
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.distributed import sharding as shd
from repro.launch.mesh import auto_mesh
from repro.models import model as M
from repro.optim.adamw import adamw_init
from repro.optim.schedule import wsd_schedule
from repro.runtime.train import make_train_step
from repro.runtime.watchdog import Heartbeat, StragglerMonitor, Watchdog


def build_mesh(spec: str):
    dims = [int(x) for x in spec.split("x")]
    n = 1
    for d in dims:
        n *= d
    if n > len(jax.devices()):
        raise SystemExit(
            f"mesh {spec} needs {n} devices, have {len(jax.devices())} "
            "(set XLA_FLAGS=--xla_force_host_platform_device_count=N for "
            "CPU experimentation)")
    names = ("data", "model") if len(dims) == 2 else \
        ("pod", "data", "model")
    return auto_mesh(tuple(dims), names[:len(dims)])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--mesh", default="1x1")
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--remat", type=int, default=1)
    ap.add_argument("--dtype", default="float32",
                    choices=["float32", "bfloat16"])
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    dtype = jnp.float32 if args.dtype == "float32" else jnp.bfloat16
    mesh = build_mesh(args.mesh)
    pipe = SyntheticLM(cfg, DataConfig(args.seq_len, args.global_batch,
                                       seed=args.seed))

    params = M.init_params(cfg, jax.random.PRNGKey(args.seed), dtype)
    params = jax.device_put(params, shd.param_specs(params, mesh))
    opt = adamw_init(params)
    step_fn = make_train_step(
        cfg,
        lr_fn=lambda s: wsd_schedule(s, peak_lr=args.lr, warmup_steps=20,
                                     total_steps=args.steps),
        grad_accum=args.grad_accum, remat=bool(args.remat)).fn
    with shd.activate_mesh(mesh):
        jitted = jax.jit(step_fn, donate_argnums=(0, 1))

        ck = Checkpointer(args.ckpt_dir) if args.ckpt_dir else None
        start = 0
        if ck is not None:
            last = latest_step(args.ckpt_dir)
            if last is not None:
                restored = ck.restore(last, {"params": params, "opt": opt})
                params, opt = restored["params"], restored["opt"]
                start = last
                print(f"[train] resumed from step {start}")

        hb = Heartbeat(0)
        monitor = StragglerMonitor()
        with Watchdog([hb], deadline_s=300.0,
                      on_dead=lambda d: print(f"[watchdog] DEAD: {d}")):
            for step in range(start, args.steps):
                t0 = time.perf_counter()
                batch = jax.device_put(pipe.batch(step),
                                       shd.batch_spec(pipe.batch(step),
                                                      mesh))
                params, opt, metrics = jitted(params, opt, batch)
                loss = float(metrics["loss"])
                dt = time.perf_counter() - t0
                hb.beat(step)
                if monitor.record(dt):
                    print(f"[straggler] step {step}: {dt:.3f}s vs median "
                          f"{monitor.median():.3f}s")
                if step % args.log_every == 0 or step == args.steps - 1:
                    tok_s = args.global_batch * args.seq_len / dt
                    print(f"[train] step {step:5d} loss {loss:8.4f} "
                          f"lr {float(metrics['lr']):.2e} "
                          f"gnorm {float(metrics['grad_norm']):8.3f} "
                          f"{dt * 1e3:7.1f} ms/step {tok_s:9.0f} tok/s",
                          flush=True)
                if not np.isfinite(loss):
                    raise RuntimeError(f"loss diverged at step {step}")
                if ck is not None and step and \
                        step % args.ckpt_every == 0:
                    ck.save_async(step, {"params": params, "opt": opt})
        if ck is not None:
            ck.save(args.steps, {"params": params, "opt": opt})
            print(f"[train] final checkpoint at step {args.steps}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
