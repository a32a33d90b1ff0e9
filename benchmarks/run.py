"""Benchmark harness: one module per paper table/figure.

Usage:
    PYTHONPATH=src python -m benchmarks.run [--quick] [--only fig7]

Prints one CSV block per figure, plus a final ``name,us_per_call,derived``
summary line per benchmark for harness compatibility.
"""

from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

from repro.consistency import benchmark_configs, split_bench_config
from repro.core import RaftParams, SimParams, run_workload

from . import (fault_matrix, fig5_lease_duration, fig6_latency,
               fig7_availability, fig8_skewness, fig11_scalability,
               fleet_matrix, gray_matrix, simperf)
from .common import emit

MATRIX_SEED = 42
MATRIX_PATH = Path(__file__).resolve().parent.parent / \
    "BENCH_consistency_matrix.json"


def consistency_matrix(quick: bool = False) -> list[dict]:
    """Fixed-seed sweep over the whole policy registry: success counts and
    latency percentiles per policy. Written to BENCH_consistency_matrix.json
    at the repo root as the machine-readable perf-trajectory artifact."""
    rows = []
    for name, config in benchmark_configs().items():
        flags, sim_flags = split_bench_config(config)
        raft = RaftParams(election_timeout=0.5, election_jitter=0.1,
                          heartbeat_interval=0.05, lease_duration=1.0,
                          **flags)
        sim = SimParams(seed=MATRIX_SEED,
                        sim_duration=1.0 if quick else 2.0,
                        interarrival=1e-3, write_fraction=1 / 3,
                        **sim_flags)
        res = run_workload(raft, sim, check=not quick, settle_time=1.0)
        s = res.summarize()

        def us(x):  # JSON has no NaN
            return None if math.isnan(x) else round(x * 1e6, 3)

        rows.append({
            "policy": name,
            "reads_ok": res.reads_ok, "reads_fail": res.reads_fail,
            "writes_ok": res.writes_ok, "writes_fail": res.writes_fail,
            "read_p50_us": us(s["read_p50"]), "read_p90_us": us(s["read_p90"]),
            "write_p50_us": us(s["write_p50"]),
            "write_p90_us": us(s["write_p90"]),
        })
    return rows


def run_consistency_matrix(quick: bool = False) -> list[dict]:
    rows = consistency_matrix(quick=quick)
    MATRIX_PATH.write_text(json.dumps(
        {"seed": MATRIX_SEED, "quick": quick, "rows": rows}, indent=2) + "\n")
    print(f"# wrote {MATRIX_PATH}", file=sys.stderr)
    return rows


FIGS = {
    "fig5_lease_duration": fig5_lease_duration.run,
    "fig6_latency": fig6_latency.run,
    "fig7_availability": fig7_availability.run,
    "fig7_headline": fig7_availability.summarize_post_election_reads,
    "fig8_skewness": fig8_skewness.run,
    "fig11_scalability": fig11_scalability.run,
    "consistency_matrix": run_consistency_matrix,
    # policy x scenario x seed nemesis sweep -> BENCH_fault_matrix.json
    # (--quick runs the CI smoke slice)
    "fault_matrix": fault_matrix.run,
    # resilience-variant x gray/corruption scenario sweep ->
    # BENCH_gray_matrix.json (--quick runs the CI smoke slice)
    "gray_matrix": gray_matrix.run,
    # policy x fleet-scenario x seed checkpoint-lineage sweep + scale
    # sweep -> BENCH_fleet_matrix.json (--quick runs the CI smoke slice)
    "fleet_matrix": fleet_matrix.run,
    # simulator wall-time baseline -> BENCH_simperf.json
    # (--quick runs the smoke slice and checks for >30% regression)
    "simperf": simperf.run,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()

    summary = []
    for name, fn in FIGS.items():
        if args.only and args.only not in name:
            continue
        print(f"\n== {name} ==", flush=True)
        t0 = time.time()
        rows = fn(quick=args.quick)
        dt = time.time() - t0
        emit(rows)
        summary.append((name, dt * 1e6 / max(1, len(rows)), len(rows)))

    print("\nname,us_per_call,derived")
    for name, us, n in summary:
        print(f"{name},{us:.1f},rows={n}")


if __name__ == "__main__":
    main()
