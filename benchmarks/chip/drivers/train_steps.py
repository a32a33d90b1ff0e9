"""Pretraining steps through the program's training entry point
(``launch/train.run_training``), with the control-plane traffic the
program makes: a step report and a heartbeat through Raft every step, and
the final checkpoint committed through Raft.

``run_training`` builds its compiled step inside the call, so set-up and
window are one call: its first ``untimed_steps`` steps compile the step
and warm it up, and the window is the fixed number of steps after them
that fills ``--seconds`` at ``nominal_step_s`` (the step time measured
on the chip when the cell was made). The work is therefore the same on
every run; a faster program ends its window sooner. The only save is the
one ``run_training`` always makes after its last step.

The benchmark sees the steps only through the registry it passes in: a
proxy that times every call, opens and closes the window at the
heartbeats of the last untimed and the last timed step, and, after the
first and the last untimed step, reads the state ``run_training`` holds
for the output check.
"""

from __future__ import annotations

import gc
import hashlib
import json
import math
import shutil
import sys
import time

import jax
import jax.numpy as jnp

import reference
import harness
from harness import Run, peak_bytes
from repro.configs.base import ShapeConfig
from repro.coord.registry import ClusterRegistry
from repro.launch.train import run_training


class TimedRegistry:
    """Stands in for the ``ClusterRegistry`` that ``run_training`` is
    given, and passes every call on to it."""

    def __init__(self, inner: ClusterRegistry, spec, untimed: int,
                 steps: int, b1: float, clip: float):
        self.inner = inner
        self.spec = spec
        self.untimed, self.steps = untimed, steps
        self.b1, self.clip = b1, clip
        self.calls: list[tuple[str, float, float]] = []
        self.heartbeats = 0
        self.first_grad_norms = None
        self.params_after_untimed = None

    def __getattr__(self, name):
        attr = getattr(self.inner, name)
        if not callable(attr):
            return attr

        def call(*args, **kwargs):
            t = time.perf_counter()
            with jax.profiler.TraceAnnotation(f"bench:registry.{name}"):
                out = attr(*args, **kwargs)
            self.calls.append((name, t, time.perf_counter()))
            if name == "report_step_time":
                self._after_step(args[1], _run_training_locals())
            elif name == "heartbeat":
                self.heartbeats += 1
                if self.heartbeats == self.untimed:
                    self.spec.window.start()
                elif self.heartbeats == self.steps:
                    self.spec.window.stop()
            return out
        return call

    def _after_step(self, step: int, frame: dict) -> None:
        """Read the state run_training holds after a step of set-up,
        before the next step is handed (and donates) it: the dict with
        the parameters and the optimizer's state, and the step's metrics,
        whatever the locals are called."""
        state = _local_with(frame, "params", "opt")
        if step == 0:
            # m after one step is (1 - b1) g, g clipped to ``clip``
            metrics = _local_with(frame, "grad_norm")
            scale = max(1.0, float(metrics["grad_norm"]) / self.clip)
            self.first_grad_norms = {
                k: v * scale / (1 - self.b1)
                for k, v in reference.leaf_norms(state["opt"]["m"]).items()}
        if step == self.untimed - 1:
            # on the host, so the window runs with the program's memory
            self.params_after_untimed = jax.device_get(state["params"])


def _run_training_locals() -> dict:
    """The locals of the innermost ``run_training`` call on the stack,
    found by its code object, so a registry call made from a helper of
    run_training finds the same frame."""
    frame = sys._getframe(1)
    while frame is not None and frame.f_code is not run_training.__code__:
        frame = frame.f_back
    if frame is None:
        raise LookupError("the registry was called from outside run_training")
    return frame.f_locals


def _local_with(frame: dict, *keys: str) -> dict:
    """The dict of device arrays among ``frame``'s values that holds
    ``keys`` (run_training also holds the state's shapes, as a template)."""
    for value in frame.values():
        if (isinstance(value, dict) and all(k in value for k in keys)
                and isinstance(jax.tree.leaves(value)[0], jax.Array)):
            return value
    raise LookupError(f"run_training holds no dict with {keys} after a step")


def _sha256(path) -> str:
    digest = hashlib.sha256()
    with open(path, "rb") as f:
        for chunk in iter(lambda: f.read(1 << 20), b""):
            digest.update(chunk)
    return digest.hexdigest()


def run(spec) -> Run:
    p, arch, cfg = spec.traffic, spec.arch, spec.ref_cfg
    untimed = p["untimed_steps"]
    timed = max(1, math.ceil(spec.seconds / p["nominal_step_s"]))
    steps = untimed + timed
    shape = ShapeConfig("bench", "train", p["seq_len"], p["global_batch"])
    ckpt_dir = spec.scratch / "ckpt"
    shutil.rmtree(ckpt_dir, ignore_errors=True)
    adamw = cfg["adamw"]
    reg = TimedRegistry(ClusterRegistry(), spec, untimed, steps,
                        adamw["b1"], adamw["clip_norm"])
    out = run_training(arch, shape, steps, str(ckpt_dir), ckpt_every=steps,
                       registry=reg, log_every=steps)
    t_end = time.perf_counter()
    memory = peak_bytes()
    w = spec.window
    losses = out["losses"]
    del out
    gc.collect()

    in_window = [t1 - t0 for name, t0, t1 in reg.calls
                 if w.t0 <= t0 and t1 <= w.t1]
    tokens = timed * p["global_batch"] * p["seq_len"]
    failed = sum(not math.isfinite(x) for x in losses[untimed:])

    # coordination: the committed manifest, read back through the lease,
    # names the step and the bytes that were saved
    manifest = reg.inner.latest_checkpoint()
    mismatch = 3
    if manifest is not None:
        on_disk = json.loads((ckpt_dir / f"step_{steps}" / "manifest.json")
                             .read_text())
        mismatch = (int(manifest != on_disk) + int(manifest["step"] != steps)
                    + int(_sha256(ckpt_dir / f"step_{steps}" / "arrays.npz")
                          != manifest["sha256"]))
    shutil.rmtree(ckpt_dir, ignore_errors=True)

    batches = [reference.synth_batch(cfg, cfg["data"], p["global_batch"],
                                     p["seq_len"], s) for s in range(untimed)]
    ref = reference.train_reference(cfg, batches, steps,
                                    rows=p["reference_rows"])
    start = reference.all_weights(cfg)
    change = reference.leaf_norms(jax.tree.map(
        lambda a, b: a.astype(jnp.float32) - b,
        reg.params_after_untimed, start))
    gaps = harness.train_gaps(
        {"losses": losses[:untimed], "first_grad_norms": reg.first_grad_norms,
         "change_norms": change}, ref)
    checks = {k: (v, p["limits"][k]) for k, v in gaps.items()}
    checks["ckpt_manifest_mismatch"] = (mismatch, 0)
    return Run(
        end_to_end={"setup_s": w.t0 - spec.t_start,
                    "train_tokens_per_s": tokens / w.seconds,
                    "ckpt_save_s": t_end - w.t1},
        attempted=timed, failed=failed, checks=checks,
        memory_peak_bytes=memory,
        work={"timed_steps": timed, "tokens": tokens,
              "seq_len": p["seq_len"], "global_batch": p["global_batch"],
              "coord_call_s": in_window})
