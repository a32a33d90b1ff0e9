"""The serving batches a profiler trace holds, and device time under a
scope within them.

The trace may stop recording the device before the window ends (seen on
a v5e after some six million operations), while the host's spans go on.
A reader that divides the work of every batch in the window by the device
time the trace holds then reads too high. These count only the batches
whose programs the trace holds. Batches are told apart on the device's
own events: each batch runs one prefill program, then its decode
programs, and the trace starts before the window's first batch, so the
k-th prefill program of the trace starts the window's k-th batch. (The
host's spans are not used to bound them: the device's clock runs about a
millisecond off the host's, and a prefill starts within that of its
``engine.generate`` span.)
"""

from __future__ import annotations

import bisect

PREFILL = "vmemkernel_flash_attention"
DECODE = "vmemkernel_decode_attention"
# XLA lowers ``jax.lax.ragged_dot`` on the TPU to kernels whose metadata
# names only the kernel (``ragged-dot-metadata``, ``ragged-dot-none``):
# the framework path, and the ``jax.named_scope`` in it, are lost.
GROUPED_PRODUCT = "ragged-dot"


def held_batches(trace, decode_steps: int | None = None
                 ) -> list[tuple[int, float, float]]:
    """(index in the window, start, end) of each batch whose prefill
    program the trace holds, finished (a decode program follows it), and
    with ``decode_steps``, each of its decode programs too. A batch spans
    its prefill program's start to its last decode program's end."""
    prefills = sorted(s for s, _ in trace.runs_with_scope(PREFILL))
    decodes = sorted(trace.runs_with_scope(DECODE))
    starts = [s for s, _ in decodes]
    out = []
    for i, a in enumerate(prefills):
        b = prefills[i + 1] if i + 1 < len(prefills) else trace.end
        mine = decodes[bisect.bisect_right(starts, a):
                       bisect.bisect_left(starts, b)]
        if mine and (decode_steps is None or len(mine) == decode_steps):
            out.append((i, a, mine[-1][1]))
    return out


def scope_time_in(trace, scope: str, intervals,
                  kernels: tuple[str, ...] = ()) -> float:
    """Device time of the first chip's operations under ``scope``, and of
    those whose own path or name starts with one of ``kernels``, that
    start inside one of ``intervals`` (control flow left out)."""
    intervals = sorted(intervals)
    starts = [a for a, _ in intervals]
    total = 0.0
    for e in trace.devices[0]["ops"]:
        if e.container or not (
                scope in e.scopes
                or (e.path or e.name).startswith(kernels)):
            continue
        i = bisect.bisect_right(starts, e.start) - 1
        if i >= 0 and e.start <= intervals[i][1]:
            total += e.end - e.start
    return total
