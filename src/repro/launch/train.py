"""End-to-end training driver with the LeaseGuard control plane.

Every run:
  * registers with the cluster registry (membership),
  * restores from the latest **committed** checkpoint manifest (leased
    zero-roundtrip read) if one exists,
  * trains with the jitted microbatched train_step,
  * reports per-step times (straggler table),
  * commits a checkpoint manifest through the Raft log every
    ``--ckpt-every`` steps,
  * optionally injects a coordinator-leader crash mid-run (--failover-at)
    to demonstrate that training does not block on coordinator failover
    (deferred-commit writes + inherited-lease reads).

Presets: ``tiny`` (CPU-friendly demo), ``100m`` (~100M-param model —
the deliverable driver; a few hundred steps on real hardware).

Usage:
  PYTHONPATH=src python -m repro.launch.train --preset tiny --steps 30
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-8b --smoke
"""

from __future__ import annotations

import argparse
import dataclasses
import time
from functools import partial

import jax
import jax.numpy as jnp
from jax.profiler import StepTraceAnnotation, TraceAnnotation

from ..configs import get_arch
from ..configs.base import ArchConfig, ShapeConfig
from ..coord.kvstore import LocalCoordinator
from ..coord.registry import ClusterRegistry
from ..train.checkpoint import restore_checkpoint, save_checkpoint
from ..train.data import DataIterator
from ..train.optimizer import OptConfig
from ..train.train_step import init_train_state, train_step
from .runtime import describe_devices, enable_compile_cache

PRESETS = {
    "tiny": ArchConfig(
        name="tiny-12m", family="dense", n_layers=4, d_model=256,
        n_heads=4, n_kv_heads=2, d_ff=1024, vocab_size=4096,
        grad_accum=1, param_dtype="float32"),
    "100m": ArchConfig(
        name="lm-100m", family="dense", n_layers=12, d_model=768,
        n_heads=12, n_kv_heads=4, d_ff=2304, vocab_size=32000,
        grad_accum=1, param_dtype="float32"),
}


def run_training(cfg: ArchConfig, shape: ShapeConfig, steps: int,
                 ckpt_dir: str, ckpt_every: int = 20,
                 registry: ClusterRegistry | None = None,
                 worker_id: str = "worker-0",
                 failover_at: int | None = None,
                 log_every: int = 5) -> dict:
    registry = registry or ClusterRegistry()
    registry.register_worker(worker_id, {"arch": cfg.name})

    # warmup proportional to short runs: a 40-step demo should not spend
    # half its budget below full LR
    opt_cfg = OptConfig(name=cfg.optimizer,
                        warmup_steps=min(20, max(2, steps // 10)),
                        total_steps=max(steps, 100))
    latest = registry.latest_checkpoint()
    template = jax.eval_shape(
        partial(init_train_state, jax.random.PRNGKey(0), cfg, opt_cfg))
    if latest is not None and latest["extra"].get("arch") == cfg.name:
        state = restore_checkpoint(template, latest)
        start_step = int(latest["step"])
        print(f"[train] resumed from committed step {start_step} "
              f"(leased read, zero roundtrips)")
    else:
        state = init_train_state(jax.random.PRNGKey(0), cfg, opt_cfg)
        start_step = 0

    data = DataIterator(cfg, shape, start_step=start_step)
    step_fn = jax.jit(partial(train_step, cfg=cfg, opt_cfg=opt_cfg),
                      donate_argnums=(0,))

    losses = []
    for step in range(start_step, steps):
        # profiler spans: the step and its host phases (no device work)
        with StepTraceAnnotation("train.step", step_num=step):
            with TraceAnnotation("train.batch"):
                batch = {k: jnp.asarray(v) for k, v in next(data).items()}
            t0 = time.time()
            with TraceAnnotation("train.dispatch"):
                state, metrics = step_fn(state, batch)
            with TraceAnnotation("train.loss_wait"):
                loss = float(metrics["loss"])
            dt = time.time() - t0
            losses.append(loss)
            with TraceAnnotation("train.report"):
                registry.report_step_time(worker_id, step, dt)
                registry.heartbeat(worker_id)  # feeds live_workers(ttl=...)
            if failover_at is not None and step == failover_at:
                crashed = registry.coord.crash_leader()
                print(f"[train] coordinator leader {crashed} crashed at "
                      f"step {step}; training continues through failover")
            if step % log_every == 0 or step == steps - 1:
                print(f"[train] step {step:5d} loss {loss:.4f} "
                      f"({dt:.2f}s)", flush=True)
            if (step + 1) % ckpt_every == 0 or step == steps - 1:
                manifest = save_checkpoint(
                    ckpt_dir, step + 1, state,
                    extra={"arch": cfg.name, "data": data.state()},
                    registry=registry)
                print(f"[train] checkpoint step {step+1} committed via "
                      f"Raft (sha {manifest['sha256'][:10]})")
    stats = registry.coord.stats()
    print(f"[train] coordinator stats: {stats}")
    flags = registry.straggler_flags()
    if any(flags.values()):
        print(f"[train] stragglers flagged: {flags}")
    return {"losses": losses, "state": state, "registry": registry}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", choices=sorted(PRESETS), default=None)
    ap.add_argument("--arch", default=None)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config of --arch")
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--failover-at", type=int, default=None)
    args = ap.parse_args()
    enable_compile_cache()
    print(f"[train] devices: {describe_devices()}")

    if args.preset:
        cfg = PRESETS[args.preset]
    elif args.arch:
        cfg = get_arch(args.arch)
        if args.smoke:
            cfg = cfg.reduced()
    else:
        cfg = PRESETS["tiny"]
    shape = ShapeConfig("cli", "train", args.seq, args.batch)
    run_training(cfg, shape, args.steps, args.ckpt_dir,
                 ckpt_every=args.ckpt_every, failover_at=args.failover_at)


if __name__ == "__main__":
    main()
