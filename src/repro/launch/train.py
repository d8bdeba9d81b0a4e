"""End-to-end training driver.

  PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b \
      --reduced --steps 200 --batch 8 --seq 128 --ckpt-dir /tmp/run1

Features: auto-mesh over available devices, sharded train state, synthetic
deterministic data, async checkpointing + auto-resume (crash/preemption
safe), straggler watchdog, optional approximate-multiplier mode (--mult),
optional int8-compressed gradient all-reduce (--compress-grads, shard_map
path), elastic restore (checkpoints reshard onto whatever mesh exists).
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.data import synthetic
from repro.launch import compile_cache
from repro.launch.mesh import make_host_mesh
from repro.train import checkpoint as ckpt
from repro.train import fault
from repro.train import train_step as ts


def main(argv=None) -> int:
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--reduced", action="store_true",
                    help="tiny same-family config (CPU-scale)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--mult", default="",
                    help="approximate multiplier (paper mode)")
    ap.add_argument("--kernel-policy", default="",
                    choices=["", "auto", "pallas", "xla"],
                    help="Pallas/XLA GEMM dispatch (kernels/dispatch.py); "
                         "'pallas' on CPU runs kernels in interpret mode")
    ap.add_argument("--optimizer", default="adamw",
                    choices=["adamw", "adafactor"])
    ap.add_argument("--moment-dtype", default="f32",
                    choices=["f32", "bf16", "int8"])
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--d-model", type=int, default=0,
                    help="override width (e.g. ~100M quickstart)")
    ap.add_argument("--n-layers", type=int, default=0)
    args = ap.parse_args(argv)

    over = {}
    if args.d_model:
        over["d_model"] = args.d_model
        over["n_heads"] = max(4, args.d_model // 64)
        over["n_kv_heads"] = max(2, args.d_model // 128)
        over["d_ff"] = args.d_model * 3
        over["head_dim"] = 64
    if args.n_layers:
        over["n_layers"] = args.n_layers
    cfg = configs.apply_overrides(configs.get_config(args.arch),
                                  reduced=args.reduced, mult=args.mult,
                                  kernel_policy=args.kernel_policy, **over)

    mesh = make_host_mesh()
    options = ts.StepOptions(
        accum_steps=args.accum, optimizer=args.optimizer,
        moment_dtype=args.moment_dtype, lr=args.lr,
        total_steps=args.steps, warmup_steps=max(10, args.steps // 20))
    init_fn, step_fn, st_sh = ts.make_train_step(cfg, options, mesh,
                                                 donate=False)

    guard = fault.PreemptionGuard()
    guard.install()
    watchdog = fault.StragglerWatchdog(
        on_straggler=lambda s, d, m: print(
            f"[fault] straggler at step {s}: {d:.3f}s vs median {m:.3f}s"))

    mgr = ckpt.CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    start_step = 0
    state = None
    if mgr is not None and mgr.latest_step() is not None:
        target = jax.eval_shape(init_fn, jax.random.key(args.seed))
        state, start_step = mgr.restore(target, shardings=st_sh)
        print(f"[train] resumed from step {start_step}")
    if state is None:
        state = jax.device_put(init_fn(jax.random.key(args.seed)), st_sh)

    losses = []
    t_start = time.time()
    for step in range(start_step, args.steps):
        watchdog.step_start()
        batch_np = synthetic.batch_for(cfg, "train", args.batch, args.seq,
                                       step, args.seed)
        batch = {k: jnp.asarray(v) for k, v in batch_np.items()}
        state, metrics = step_fn(state, batch)
        loss = float(metrics["loss"])
        losses.append(loss)
        watchdog.step_end(step)
        if step % args.log_every == 0 or step == args.steps - 1:
            dt = time.time() - t_start
            print(f"[train] step {step:5d} loss {loss:8.4f} "
                  f"gnorm {float(metrics['gnorm']):8.3f} "
                  f"({dt / max(step - start_step + 1, 1):.2f}s/step)")
        if mgr is not None and (step + 1) % args.ckpt_every == 0:
            mgr.save(state, step + 1, blocking=False)
        if guard.preempted:
            print("[train] preemption requested: checkpointing + exit")
            if mgr is not None:
                mgr.save(state, step + 1, blocking=True)
            return 0
    if mgr is not None:
        mgr.save(state, args.steps, blocking=True)
    if not losses:  # resumed from a checkpoint of the last step
        print(f"[train] done: no steps left after step {start_step}")
        return 0
    first = np.mean(losses[:5]) if len(losses) >= 5 else losses[0]
    last = np.mean(losses[-5:])
    print(f"[train] done: loss {first:.4f} -> {last:.4f} "
          f"({len(watchdog.flagged)} straggler steps flagged)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
