"""Dry-run integration on a small host-device mesh (subprocess: jax locks
device count at first init, so the 8-device XLA flag must be set before
import). One reduced arch per family × all three step kinds, plus the
sharding-spec construction for every full-size arch."""

import json
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses, json
import jax
from repro.configs import ARCHS, get_arch
from repro.configs.base import ShapeConfig
from repro.launch.dryrun import lower_cell, input_specs
from repro.sharding.rules import param_specs, state_specs
from functools import partial
from repro.models import init_params
from repro.train.optimizer import OptConfig
from repro.train.train_step import init_train_state

mesh = jax.make_mesh((2, 4), ("data", "model"))

# 1) spec construction for every FULL config (no compile)
for name, cfg in ARCHS.items():
    shapes = jax.eval_shape(partial(init_params, jax.random.PRNGKey(0), cfg))
    specs = param_specs(shapes, mesh)
    n = len(jax.tree.leaves(specs, is_leaf=lambda x: hasattr(x, "index")))
    assert n > 0, name

# 2) compile one reduced cell per family x kind
fams = {}
for name, cfg in ARCHS.items():
    fams.setdefault(cfg.family, name)
results = {}
for fam, name in sorted(fams.items()):
    cfg = get_arch(name).reduced()
    cfg = dataclasses.replace(cfg, grad_accum=2)
    for kind, shape in [("train", ShapeConfig("t", "train", 64, 8)),
                        ("prefill", ShapeConfig("p", "prefill", 64, 8)),
                        ("decode", ShapeConfig("d", "decode", 64, 8))]:
        lowered = lower_cell(cfg, shape, mesh)
        compiled = lowered.compile()
        cost = compiled.cost_analysis()
        assert cost.get("flops", 0) >= 0
        results[f"{fam}:{kind}"] = True
print("DRYRUN_OK " + json.dumps(results))
"""


@pytest.mark.slow
def test_small_mesh_dryrun_all_families():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=1200)
    assert "DRYRUN_OK" in out.stdout, f"stdout:\n{out.stdout[-2000:]}\n" \
                                      f"stderr:\n{out.stderr[-3000:]}"
    line = [l for l in out.stdout.splitlines() if l.startswith("DRYRUN_OK")][0]
    results = json.loads(line.split(" ", 1)[1])
    # 6 families x 3 kinds
    assert len(results) == 18
