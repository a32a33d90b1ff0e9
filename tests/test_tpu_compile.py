"""Compile-only tests for one TPU v5e chip, described and not attached.

The TPU compiler refuses what interpret mode accepts: block shapes that
break the tiling, programs that do not fit the chip's memory. These tests
compile the Pallas kernels at the widths of the configured models, the
full-width h2o-danube-1.8b decode step, and the serving engine's own
prefill and decode programs at the shapes of the benchmark's serving
cells, for a chip of a described ``v5e:2x2`` topology. Nothing runs, so
they say nothing about results or times.

The topology is described inside a module-scoped fixture, never at
import time: only one process may load the TPU library, and test
collection must not depend on whether it did.
"""

import dataclasses
import os
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_arch
from repro.kernels.decode_attention import flash_decode
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.rwkv6 import wkv6_chunked
from repro.models import decode_step, init_decode_cache, init_params
from repro.serve.engine import Engine

V5E_HBM_BYTES = 16 * 2**30

BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32

# (kernel call, argument (shape, dtype) pairs). Batch 1 at a 4096-token
# sequence or cache; widths from configs/: danube hd 80, 32 q / 8 kv
# heads, window 4096; qwen2.5-3b hd 128, 16 q / 2 kv heads; rwkv6-3b
# 40 heads of hd 64.
KERNEL_CASES = {
    "flash_attention_fwd-danube": (
        partial(flash_attention_fwd, window=4096),
        [((32, 4096, 80), BF16), ((8, 4096, 80), BF16),
         ((8, 4096, 80), BF16)]),
    "flash_attention_fwd-qwen2.5-3b": (
        flash_attention_fwd,
        [((16, 4096, 128), BF16), ((2, 4096, 128), BF16),
         ((2, 4096, 128), BF16)]),
    "flash_decode-danube": (
        flash_decode,
        [((8, 4, 80), BF16), ((8, 4096, 80), BF16), ((8, 4096, 80), BF16),
         ((8,), I32)]),
    "flash_decode-qwen2.5-3b": (
        flash_decode,
        [((2, 8, 128), BF16), ((2, 4096, 128), BF16),
         ((2, 4096, 128), BF16), ((2,), I32)]),
    "wkv6_chunked-rwkv6-3b": (
        wkv6_chunked,
        [((40, 512, 64), F32)] * 4 + [((40, 64), F32)]),
}


@pytest.fixture(scope="module")
def topo():
    # keep the compiler's logs out of the shared temp directory
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler installed, or it cannot load
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compilation cache off: a
    program compiled for an absent chip is written to the cache but can
    never be read back."""
    from jax.experimental.compilation_cache import compilation_cache
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_enabled)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_kernel_compiles_for_v5e(case, one_chip):
    fn, args = KERNEL_CASES[case]
    shapes = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in args]
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _on_chip(tree, sharding):
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=sharding), tree)


def test_danube_decode_step_fits_one_v5e(one_chip):
    cfg = get_arch("h2o-danube-1.8b")
    params = _on_chip(jax.eval_shape(
        partial(init_params, jax.random.PRNGKey(0), cfg)), one_chip)
    caches = _on_chip(jax.eval_shape(
        partial(init_decode_cache, cfg, 4, cfg.sliding_window)), one_chip)
    ids = jax.ShapeDtypeStruct((4,), jnp.int32, sharding=one_chip)
    step = jax.jit(lambda p, tok, c, pos: decode_step(p, cfg, tok, c, pos))
    compiled = step.lower(params, ids, caches, ids).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes > 3.5e9   # the full bf16 weights
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < V5E_HBM_BYTES


# The served shapes of the benchmark's cells: (config, batch, prompt
# length, decode cache length = prompt + new tokens, bytes the weights
# exceed). danube chat: 32 prompts of 256 tokens, 64 new; danube docqa:
# 4 of 4080, 16 new, filling the 4096 window; Moonlight, one chip's
# 8-expert share: 64 of 1024, 128 new (3.36 B parameters, 6.73 GB).
SERVED = {
    "danube-chat": ("h2o-danube-1.8b", 0, 32, 256, 320, 3.5e9),
    "danube-docqa": ("h2o-danube-1.8b", 0, 4, 4080, 4096, 3.5e9),
    "moonlight-ep8": ("moonlight-16b-a3b", 8, 64, 1024, 1152, 6.5e9),
}
SCOPES = {"prefill": "vmemkernel_flash_attention",
          "decode": "vmemkernel_decode_attention"}


def _decode_programs(served, one_chip):
    """The engine's decode step and cache growth for a served shape,
    compiled as Engine.generate builds them, and the decode caches'
    shapes."""
    arch, held, batch, prompt, cache_len, _ = SERVED[served]
    cfg = dataclasses.replace(get_arch(arch), experts_held=held)
    params = _on_chip(jax.eval_shape(
        partial(init_params, jax.random.PRNGKey(0), cfg)), one_chip)
    engine = Engine(cfg, params)
    prefilled = _on_chip(jax.eval_shape(
        partial(init_decode_cache, cfg, batch, prompt)), one_chip)
    ids = jax.ShapeDtypeStruct((batch,), I32, sharding=one_chip)
    decode, grow = engine._programs(ids, prefilled, ids, cache_len - prompt)
    caches = jax.eval_shape(partial(init_decode_cache, cfg, batch,
                                    cache_len))
    return decode, grow, caches


def _bytes(tree) -> int:
    return sum(c.size * c.dtype.itemsize for c in jax.tree.leaves(tree))


@pytest.mark.parametrize("program", ["prefill", "decode"])
@pytest.mark.parametrize("served", sorted(SERVED))
def test_engine_program_fits_one_v5e(served, program, one_chip):
    """The programs Engine.generate runs, compiled at full width: the
    weights, the program's inputs, outputs and scratch fit one chip's
    memory, and the decode step's new caches take the donated caches'
    buffers."""
    arch, held, batch, prompt, cache_len, weight_bytes = SERVED[served]
    if program == "prefill":
        cfg = dataclasses.replace(get_arch(arch), experts_held=held)
        params = _on_chip(jax.eval_shape(
            partial(init_params, jax.random.PRNGKey(0), cfg)), one_chip)
        tokens = jax.ShapeDtypeStruct((batch, prompt), I32,
                                      sharding=one_chip)
        compiled = Engine(cfg, params)._prefill.lower(params,
                                                      tokens).compile()
    else:
        compiled, _, caches = _decode_programs(served, one_chip)
        assert compiled.memory_analysis().alias_size_in_bytes \
            >= _bytes(caches)
    # the benchmark's readers find each program by its attention's scope
    assert SCOPES[program] in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes > weight_bytes   # full bf16 weights
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes) \
        < V5E_HBM_BYTES


@pytest.mark.parametrize("served", sorted(SERVED))
def test_decode_step_updates_the_caches_in_place(served, one_chip):
    """The decode step writes its rows into the caches it is given: next
    to no scratch, no copy of a whole stacked cache (as a layer scan that
    returns new caches, or a change of their layout, would make), and the
    caches come back in the layout they went in, in which the growth
    program hands them over, itself with next to no scratch."""
    decode, grow, caches = _decode_programs(served, one_chip)
    cache_bytes = _bytes(caches)
    assert decode.memory_analysis().temp_size_in_bytes < 0.05 * cache_bytes
    assert grow.memory_analysis().temp_size_in_bytes < 0.05 * cache_bytes
    stacks = {"[" + ",".join(map(str, c.shape)) + "]"
              for c in jax.tree.leaves(caches) if c.ndim >= 4}
    copies = [line.split(" = ")[1].split(" ")[0]
              for line in decode.as_text().splitlines()
              if " copy(" in line or " copy-start(" in line]
    assert not [c for c in copies if any(c.endswith(s) or s + "{" in c
                                         for s in stacks)], copies
    takes = decode.input_formats[0][2]
    assert jax.tree.leaves(decode.output_formats[1]) == \
        jax.tree.leaves(takes)
    assert jax.tree.leaves(grow.output_formats) == jax.tree.leaves(takes)
