"""End-to-end training driver with fault tolerance.

    PYTHONPATH=src python -m repro.launch.train --arch smollm-135m-smoke \\
        --steps 200 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt [--qat]

Production behaviours exercised here (scaled to the container):
  * restart-from-latest: the driver always tries to restore before training —
    kill it at any step and re-launch to resume (tests/test_system.py does
    exactly that with a simulated preemption),
  * atomic async checkpoints every --ckpt-every steps,
  * deterministic data: batch content is a pure function of (seed, step),
  * straggler watchdog: steps slower than --straggler-factor × the running
    median are logged (on real fleets this feeds the health controller that
    triggers elastic down-scaling; here it logs),
  * elastic restore: --mesh data,model can differ between runs — the restore
    path device_puts onto the new topology.
"""
from __future__ import annotations

import argparse
import statistics
import time

import jax
import jax.numpy as jnp

from repro.core.policy import QuantPolicy
from repro.data.pipeline import DataPipeline, markov_batch_fn
from repro.dist import sharding as shd
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models.registry import get_config
from repro.optim import adamw, multistep_lr, sgd
from repro.train.checkpoint import CheckpointManager
from repro.train.trainer import make_train_step


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--optimizer", default="adamw", choices=["adamw", "sgd"])
    ap.add_argument("--qat", action="store_true", help="int8 QAT (paper 4.3)")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--mesh", default="1,1", help="data,model")
    ap.add_argument("--microbatch", type=int, default=1)
    ap.add_argument("--straggler-factor", type=float, default=3.0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_config(args.arch)
    model = cfg.build(dtype=jnp.float32, remat="none")
    dm, tp = (int(x) for x in args.mesh.split(","))
    mesh = make_host_mesh(dm, tp) if dm * tp > 1 else None
    rules = shd.make_axis_rules(mesh) if mesh else None

    optimizer = (adamw(weight_decay=0.01) if args.optimizer == "adamw"
                 else sgd(momentum=0.9, weight_decay=5e-4))
    schedule = multistep_lr(args.lr, milestones=(args.steps * 2 // 3,
                                                 args.steps * 5 // 6))
    policy = QuantPolicy.int8_qat() if args.qat else QuantPolicy.float32()
    step_fn = jax.jit(make_train_step(model, optimizer, schedule,
                                      policy=policy, mesh=mesh,
                                      axis_rules=rules,
                                      microbatch_split=args.microbatch),
                      donate_argnums=(0,))

    pipe = DataPipeline(markov_batch_fn(cfg.vocab, args.batch, args.seq,
                                        seed=args.seed))

    params = model.init(jax.random.PRNGKey(args.seed))
    state = {"params": params, "opt": optimizer.init(params),
             "step": jnp.zeros((), jnp.int32)}

    ckpt = None
    if args.ckpt_dir:
        ckpt = CheckpointManager(args.ckpt_dir, keep=3)
        latest = ckpt.latest_step()
        if latest is not None:
            state = ckpt.restore(latest, state)
            pipe.restore({"step": latest})
            print(f"[restore] resumed from step {latest}")

    times = []
    start_step = int(state["step"])
    for step in range(start_step, args.steps):
        batch = next(pipe)
        t0 = time.time()
        state, metrics = step_fn(state, batch)
        metrics = jax.device_get(metrics)
        dt = time.time() - t0
        times.append(dt)
        if len(times) > 20:
            times.pop(0)
        med = statistics.median(times)
        if dt > args.straggler_factor * med and len(times) > 5:
            print(f"[straggler] step {step}: {dt:.2f}s vs median {med:.2f}s")
        if step % args.log_every == 0 or step == args.steps - 1:
            print(f"step {step:5d} loss {metrics['loss']:.4f} "
                  f"acc {metrics['accuracy']:.3f} lr {metrics['lr']:.2e} "
                  f"{dt*1e3:.0f}ms")
        if ckpt and (step + 1) % args.ckpt_every == 0:
            ckpt.save_async(step + 1, state)
    if ckpt:
        ckpt.save(args.steps, state)
        ckpt.wait()
    print("done")
    return state


if __name__ == "__main__":
    main()
