"""The profiler spans of the serving engine, the training loop, the
checkpoint save and the coordinator, read back from a trace recorded on
the CPU; and the engine's output, which the profiler must not change."""

from pathlib import Path

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs.base import ShapeConfig
from repro.coord.registry import ClusterRegistry
from repro.launch.serve import random_prompts, start_engine
from repro.launch.train import PRESETS, run_training
from repro.serve.engine import ServeConfig

TINY = PRESETS["tiny"]
NEW_TOKENS = 4


def _host_spans(directory: Path, prefix: str) -> list[tuple[str, int, int]]:
    """(name, start, end) in ns of the host spans whose name starts with
    ``prefix``, in order of start."""
    path = sorted(directory.glob("**/*.xplane.pb"))[-1]
    data = ProfileData.from_serialized_xspace(path.read_bytes())
    host = next(p for p in data.planes if p.name == "/host:CPU")
    spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
             for line in host.lines for e in line.events
             if e.name.startswith(prefix)]
    return sorted(spans, key=lambda s: s[1])


def _inside(span, outer) -> bool:
    return outer[1] <= span[1] and span[2] <= outer[2]


def _named(spans, name):
    return [s for s in spans if s[0] == name]


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """One batch served with the profiler off, then the same batch with
    it on: the outputs of both and the spans of the second."""
    engine = start_engine(TINY, ServeConfig(max_new_tokens=NEW_TOKENS))
    prompts = random_prompts(TINY, 2, 8)
    plain = engine.generate(prompts, return_logits=True)
    trace_dir = tmp_path_factory.mktemp("engine_trace")
    with jax.profiler.trace(str(trace_dir)):
        traced = engine.generate(prompts, return_logits=True)
    return plain, traced, _host_spans(trace_dir, "engine.")


def test_generate_has_one_of_each_batch_phase(served):
    spans = served[2]
    for name in ("engine.generate", "engine.prefill", "engine.grow_cache",
                 "engine.fetch"):
        assert len(_named(spans, name)) == 1, (name, spans)
    generate = _named(spans, "engine.generate")[0]
    assert all(_inside(s, generate) for s in spans)
    # the first token's sample, then one in each decode step
    assert len(_named(spans, "engine.sample")) == NEW_TOKENS


def test_each_decode_step_holds_one_sample(served):
    spans = served[2]
    steps = _named(spans, "engine.decode_step")
    assert len(steps) == NEW_TOKENS - 1
    samples = _named(spans, "engine.sample")
    for step in steps:
        assert sum(_inside(s, step) for s in samples) == 1, (step, spans)
    # prefill, cache growth and the first sample come before the steps,
    # the fetch after them
    first, last = steps[0], steps[-1]
    for name in ("engine.prefill", "engine.grow_cache"):
        assert _named(spans, name)[0][2] <= first[1]
    assert samples[0][2] <= first[1]
    assert _named(spans, "engine.fetch")[0][1] >= last[2]


def test_profiler_leaves_the_output_bit_for_bit(served):
    (ids, logits), (ids_t, logits_t), _ = served
    assert ids.shape == (2, NEW_TOKENS)
    assert logits.shape == (2, NEW_TOKENS, TINY.vocab_size)
    np.testing.assert_array_equal(ids_t, ids)
    assert logits_t.dtype == logits.dtype
    assert logits_t.tobytes() == logits.tobytes()


@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    """Two training steps at ``tiny`` under the profiler: the spans and
    the coordinator's counters."""
    root = tmp_path_factory.mktemp("train_trace")
    with jax.profiler.trace(str(root / "trace")):
        registry = ClusterRegistry()
        run_training(TINY, ShapeConfig("s", "train", 32, 2), 2,
                     str(root / "ckpt"), registry=registry)
    return (_host_spans(root / "trace", ""), registry.coord.stats())


def test_checkpoint_save_is_split_once(trained):
    spans = trained[0]
    saves = _named(spans, "ckpt.save")
    assert len(saves) == 1
    for name in ("ckpt.copy", "ckpt.write", "ckpt.hash", "ckpt.commit"):
        found = _named(spans, name)
        assert len(found) == 1 and _inside(found[0], saves[0]), name


def test_each_train_step_holds_its_phases(trained):
    spans = trained[0]
    steps = _named(spans, "train.step")
    assert len(steps) == 2
    for name in ("train.batch", "train.dispatch", "train.loss_wait",
                 "train.report"):
        found = _named(spans, name)
        assert [sum(_inside(s, step) for s in found)
                for step in steps] == [1, 1], name


def test_coordinator_counts_what_its_spans_mark(trained):
    spans, stats = trained
    appends = _named(spans, "coord.append")
    # registration, a step report and a heartbeat a step, the manifest
    assert stats["appends"] == len(appends) >= 1 + 2 * 2 + 1
    assert stats["reads"] == len(_named(spans, "coord.read")) >= 1
    # each append is replicated to the two followers at least
    assert stats["append_messages"] >= 2 * stats["appends"]
    # the step reports and heartbeats are made inside train.report
    reports = _named(spans, "train.report")
    assert sum(_inside(a, r) for a in appends for r in reports) == 2 * 2
