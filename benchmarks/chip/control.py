"""Readings that set the limits of the output check: the program's own,
and those of the control (the float32 reference computed in float8
instead, the next precision below the configurations' bfloat16) and of
planted faults. The benchmark's runs never run this.

  python3 benchmarks/chip/control.py --workload danube-serve-chat \\
      --seeds 11 12 13
  python3 benchmarks/chip/control.py --workload danube-train-pretrain \\
      --seconds 30     # once the training cell is in BENCHMARK.json

Serving: one engine serves, for each seed, the first batch of that seed's
window at the cell's own size; the program's widest logit gap and the
control's are read on the same sample of requests the benchmark checks.
Training: the control and a reference that leaves half of each batch out
are followed through the same steps as the float32 reference, and read
by the numbers the benchmark compares. The training cell's inputs do not
depend on the seed, so it takes none. Prints one JSON line per reading,
with the verdict that the benchmark's own comparison (``harness.
is_correct`` against the cell's limits) gives it: the control's has to
be false.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1] / "src"))
# JAX's persistent compilation cache lives at a fixed directory inside the
# checkout, whatever the machine's environment names, so that two
# checkouts never share one; the program's enable_compile_cache keeps it.
# No size limit: with one, JAX reads an access-time file beside every
# entry, and an entry without one (seen on a v5e host) fails every
# later write, so each run compiled everything again.
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(HERE.parents[1]
                                                / ".jax_cache")
os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"


def serve_readings(cell, seeds, rehearsal=False):
    import jax.numpy as jnp
    import numpy as np
    import harness
    import reference
    from repro.launch.serve import start_engine
    from repro.serve.engine import ServeConfig
    driver = harness.load_module(HERE / "drivers" / "serve_batches.py")
    arch = harness.arch_config(cell.config, rehearsal)
    cfg = harness.reference_config(cell.config, arch)
    p = dict(cell.traffic, **(cell.traffic["rehearsal"] if rehearsal else {}))
    limit = p["limits"]["served_logit_gap"]
    engine = start_engine(arch, ServeConfig(max_new_tokens=p["new_tokens"]))
    for seed in seeds:
        prompts = driver._prompts(np.random.default_rng(
            [seed, driver.WINDOW_STREAM]), p, arch.vocab_size)
        served = engine.generate(jnp.asarray(prompts))
        pick = np.random.default_rng([seed, driver.CHECK_STREAM]).choice(
            p["batch"], size=min(p["check_requests"], p["batch"]),
            replace=False)
        reading = {"seed": seed,
                   "program": float(reference.served_gaps(
                       cfg, prompts[pick], served[pick]).max()),
                   "control_fp8": float(reference.served_gaps(
                       cfg, prompts[pick], served[pick],
                       control="fp8").max())}
        for name in ("program", "control_fp8"):
            reading[f"{name}_correct"] = harness.is_correct(
                {"served_logit_gap": (reading[name], limit)})
        yield reading


def train_readings(cell, seconds, rehearsal=False):
    import harness
    import reference
    arch = harness.arch_config(cell.config, rehearsal)
    cfg = harness.reference_config(cell.config, arch)
    p = dict(cell.traffic, **(cell.traffic["rehearsal"] if rehearsal else {}))
    # the run's length sets the learning-rate schedule, as in the driver
    steps = p["untimed_steps"] + max(1, math.ceil(seconds
                                                  / p["nominal_step_s"]))
    batches = [reference.synth_batch(cfg, cfg["data"], p["global_batch"],
                                     p["seq_len"], s)
               for s in range(p["untimed_steps"])]
    ref = reference.train_reference(cfg, batches, steps,
                                    rows=p["reference_rows"])
    half = [{k: v[: len(v) // 2] for k, v in b.items()} for b in batches]
    for name, dot, rows in (("control_fp8", "fp8", batches),
                            ("half_batch", "f32", half)):
        other = reference.train_reference(cfg, rows, steps, dot_name=dot,
                                          rows=p["reference_rows"])
        gaps = harness.train_gaps(other, ref)
        yield {"reading": name, **gaps, "correct": harness.is_correct(
            {k: (v, p["limits"][k]) for k, v in gaps.items()})}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--seconds", type=float, default=None,
                    help="training: the window's length (run_seconds)")
    args = ap.parse_args(argv)
    import jax
    import harness
    from repro.launch.runtime import enable_compile_cache
    cell = harness.load_cell(args.workload)
    refusal = harness.device_refusal(jax.devices(), cell.chips)
    if refusal:
        print(f"refused: {refusal}", file=sys.stderr)
        return 2
    enable_compile_cache()
    t = time.perf_counter()
    readings = (serve_readings(cell, args.seeds)
                if cell.traffic["driver"] == "serve_batches"
                else train_readings(cell, args.seconds))
    for r in readings:
        print(json.dumps({**r, "elapsed_s": time.perf_counter() - t}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
