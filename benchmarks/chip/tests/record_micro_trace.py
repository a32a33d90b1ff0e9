"""Record ``data/micro.xplane.pb``, the small TPU trace that the trace
reduction's tests read. Run on a TPU host from the root of a checkout:

  python3 benchmarks/chip/tests/record_micro_trace.py
"""

import shutil
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp

OUT = Path(__file__).resolve().parent / "data" / "micro.xplane.pb"


@jax.jit
def step(q, k):
    with jax.named_scope("vmemkernel_decode_attention"):
        s = jnp.einsum("bd,sd->bs", q, k, preferred_element_type=jnp.float32)
        p = jax.nn.softmax(s, axis=-1)
    return (p.astype(k.dtype) @ k).sum()


def main() -> int:
    if jax.devices()[0].platform != "tpu":
        print("records a TPU trace; no TPU here", file=sys.stderr)
        return 2
    q = jnp.ones((64, 128), jnp.bfloat16)
    k = jnp.ones((4096, 128), jnp.bfloat16)
    step(q, k).block_until_ready()
    (q * 2).block_until_ready()
    scratch = OUT.parent / "micro_trace"
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(scratch), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench:window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench:step"):
                step(q, k).block_until_ready()
            time.sleep(0.002)
        (q * 2).block_until_ready()
    jax.profiler.stop_trace()
    shutil.copy(sorted(scratch.glob("**/*.xplane.pb"))[-1], OUT)
    shutil.rmtree(scratch)
    return 0


if __name__ == "__main__":
    sys.exit(main())
