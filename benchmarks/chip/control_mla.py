"""Readings that set the limits of the latent-attention MoE cells' output
check, as ``control.py`` takes them for the dense serving cells: the
program's widest and mean logit gaps and those of the control
(``reference_mla_moe`` computed in float8 instead, the next precision
below bfloat16), read on the sample of requests the benchmark checks.
The benchmark's runs never run this.

  python3 benchmarks/chip/control_mla.py --workload moonlight-serve-chat \\
      --seeds 11 12 13

One engine serves, for each seed, the first batch of that seed's window
at the cell's own size. Prints one JSON line per seed, with the verdict
that the benchmark's own comparison (``harness.is_correct`` against the
cell's limits) gives each reading: the control's has to be false.

``--witness DIR`` adds, for each seed, what shows where the program's
widest gaps come from: the gaps of a reference whose products round
their operands to bfloat16 (``bf16``), and each checked position's
routing margin, the least over the expert layers of the k-th selected
expert's biased score over the next one's. The line gives the program's
five widest gaps with that position's bf16 gap and margin, and the share
of checked positions whose margin is below it; ``DIR/<seed>.npz`` holds
every position's.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

from control import HERE   # puts the program on the path, as control.py


def _gaps(cfg, prompts, served, witness=False):
    """``reference_mla_moe.served_gaps`` of the served tokens, of the
    control's and, with ``witness``, of the bf16 reference's, from one
    forward each; with ``witness`` the routing margins come last."""
    import numpy as np
    import reference_mla_moe
    tokens = np.concatenate([prompts, served[:, :-1]], axis=1)
    first = prompts.shape[1] - 1
    ref, margins = reference_mla_moe.logits_at(cfg, tokens, first,
                                               margins=True)
    best = ref.max(-1)
    chosen = [served] + [
        reference_mla_moe.logits_at(cfg, tokens, first, dot).argmax(-1)
        for dot in ("fp8", "bf16")[:1 + witness]]
    for c in chosen:
        yield best - np.take_along_axis(ref, c[..., None], -1)[..., 0]
    if witness:
        yield margins


def _witness(program, control, bf16, margins, out: Path,
             seed: int) -> dict:
    """Where the program's five widest gaps sit: (request, step, program
    gap, bf16 gap, margin, share of positions with a smaller margin)."""
    import numpy as np
    out.mkdir(parents=True, exist_ok=True)
    np.savez(out / f"{seed}.npz", program=program, control_fp8=control,
             bf16=bf16, margins=margins)
    flat = margins.ravel()
    widest = []
    for i in np.argsort(program, axis=None)[::-1][:5]:
        r, t = np.unravel_index(i, program.shape)
        widest.append([int(r), int(t), float(program[r, t]),
                       float(bf16[r, t]), float(margins[r, t]),
                       float(np.mean(flat < margins[r, t]))])
    return {"bf16": float(bf16.max()), "bf16_mean": float(bf16.mean()),
            "widest": widest,
            "margin_quantiles": [float(q) for q in np.quantile(
                flat, [0.01, 0.1, 0.5])]}


def serve_readings(cell, seeds, rehearsal=False, witness=None):
    import jax.numpy as jnp
    import numpy as np
    import harness
    from repro.launch.serve import start_engine
    from repro.serve.engine import ServeConfig
    driver = harness.load_module(HERE / "drivers" / "serve_batches_mla.py")
    sb = driver.serve_batches
    arch = harness.arch_config(cell.config, rehearsal)
    p = dict(cell.traffic, **(cell.traffic["rehearsal"] if rehearsal else {}))
    cfg = driver.reference_config(
        cell.config, arch, harness.reference_config(cell.config, arch))
    engine = start_engine(arch, ServeConfig(max_new_tokens=p["new_tokens"]))
    for seed in seeds:
        prompts = sb._prompts(np.random.default_rng(
            [seed, sb.WINDOW_STREAM]), p, arch.vocab_size)
        served = engine.generate(jnp.asarray(prompts))
        pick = np.random.default_rng([seed, sb.CHECK_STREAM]).choice(
            p["batch"], size=min(p["check_requests"], p["batch"]),
            replace=False)
        reading = {"seed": seed}
        gaps = list(_gaps(cfg, prompts[pick], served[pick],
                          witness is not None))
        for name, g in zip(("program", "control_fp8"), gaps):
            checks = driver.gap_checks(g, p["limits"])
            reading[name] = checks["served_logit_gap"][0]
            reading[f"{name}_mean"] = checks["served_logit_gap_mean"][0]
            reading[f"{name}_correct"] = harness.is_correct(checks)
        if witness is not None:
            reading.update(_witness(*gaps, witness, seed))
        yield reading


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--witness", type=Path, metavar="DIR")
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
    for r in serve_readings(cell, args.seeds, witness=args.witness):
        print(json.dumps({**r, "elapsed_s": time.perf_counter() - t}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
