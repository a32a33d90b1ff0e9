"""Run one cell of the on-chip benchmark and print its result line.

Usage, from the root of a checkout, on a machine with the chips the cell
asks for:

  python3 benchmarks/chip/run.py --workload danube-serve-chat \\
      --seed 1234 --seconds 30 --trace 0

``--trace 0`` reports the cell's end-to-end metrics from the host clock;
``--trace 1`` runs the same cell with the profiler on over the window and
reports its per-layer metrics. Either way the last line of standard
output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics``, ``device`` (and ``breakdown`` when traced), then ``checks``,
each number compared with its limit. The same numbers are the last lines
of standard error. A machine without a TPU, with fewer chips than the
cell asks for, or of a kind without published peaks gets no result line
and a nonzero exit.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()   # set-up counts from here

import argparse
import json
import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1] / "src"))   # the program under test
# JAX's persistent compilation cache lives at a fixed directory inside the
# checkout, whatever the machine's environment names, so that two
# checkouts never share one; the program's enable_compile_cache keeps it.
# No size limit: with one, JAX reads an access-time file beside every
# entry, and an entry without one (seen on a v5e host) fails every
# later write, so each run compiled everything again.
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(HERE.parents[1]
                                                / ".jax_cache")
os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"


def seed_arg(text: str) -> int:
    seed = int(text)
    if seed < 0:
        raise argparse.ArgumentTypeError("the seed is a whole number >= 0")
    return seed


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=seed_arg, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import harness
    cell = harness.load_cell(args.workload)
    import jax
    refusal = harness.device_refusal(jax.devices(), cell.chips)
    if refusal:
        print(f"refused: {refusal}", file=sys.stderr)
        return 2
    from repro.launch.runtime import enable_compile_cache
    enable_compile_cache()

    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), T_START)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
