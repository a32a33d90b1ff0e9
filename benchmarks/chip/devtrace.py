"""Reduction of a profiler trace (``.xplane.pb``) to what the per-layer
metrics read: when the device was busy, which programs ran, how long the
operations under a named scope took, and what the host was doing in the
device's idle gaps.

What a TPU trace holds, as recorded on a TPU v5e with this JAX:

* the plane ``/device:TPU:<n>`` has a line ``XLA Modules``, one event
  per program execution named ``<module>(<fingerprint>)``, and a line
  ``XLA Ops``, one event per operation named by its HLO text
  (``%fusion.5 = ...``); a ``while`` operation's event spans its body's
  operations, which have events of their own;
* the plane ``/host:metadata`` holds, for each module, its HLO proto.
  Each instruction's metadata carries the framework path it was traced
  under (``jit(f)/vmemkernel_decode_attention/dot_general``), and so the
  ``jax.named_scope`` names. A fusion is under a scope when any
  instruction fused into it is, so a fusion that mixes a scope's work
  with other work counts whole: the scope's time is never undercounted;
* the plane ``/host:CPU`` holds the host's spans: the benchmark's own
  (``jax.profiler.TraceAnnotation``, named ``bench:...``) and JAX's.

Host and device events share one clock in the trace, to about a
millisecond. Times here are in seconds.
"""

from __future__ import annotations

import bisect
import re
from dataclasses import dataclass
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE, METADATA_PLANE = "/host:CPU", "/host:metadata"
OPS_LINE, PROGRAMS_LINE = "XLA Ops", "XLA Modules"
WINDOW_SPAN = "bench:window"
CONTAINERS = frozenset({"while", "conditional", "call"})


@dataclass(frozen=True)
class Event:
    name: str
    start: float
    end: float
    path: str = ""           # an operation's own framework path
    scopes: str = ""         # every framework path fused into it
    container: bool = False  # a control-flow op whose body has own events


def union_s(intervals) -> float:
    """Total length covered by possibly overlapping intervals."""
    total, reach = 0.0, float("-inf")
    for start, end in sorted(intervals):
        if end > reach:
            total += end - max(start, reach)
            reach = end
    return total


def gaps(intervals, start: float, end: float) -> list[tuple[float, float]]:
    """The parts of [start, end] that no interval covers."""
    out, reach = [], start
    for s, e in sorted(intervals):
        if s > reach:
            out.append((reach, min(s, end)))
        reach = max(reach, e)
        if reach >= end:
            break
    if reach < end:
        out.append((reach, end))
    return [(s, e) for s, e in out if e > s]


class Trace:
    def __init__(self, devices: list[dict], host: list[Event]):
        """``devices``: one dict per chip with its ``ops`` and ``programs``
        (lists of :class:`Event`); ``host``: the host's spans.

        The profiler runs from just before the window to just after it,
        so every device event in the trace is the window's. The window
        is the host span ``bench:window`` widened to hold them all: the
        device's clock can sit a millisecond off the host's."""
        self.devices = devices
        self.host = host
        evs = [e for d in devices for e in d["ops"] + d["programs"]]
        spans = [(e.start, e.end) for e in host if e.name == WINDOW_SPAN]
        spans += [(e.start, e.end) for e in evs]
        self.start = min(s for s, _ in spans)
        self.end = max(e for _, e in spans)

    @classmethod
    def from_dir(cls, directory: Path) -> "Trace":
        files = sorted(Path(directory).glob("**/*.xplane.pb"))
        if not files:
            raise FileNotFoundError(f"no .xplane.pb under {directory}")
        return cls.from_file(files[-1])

    @classmethod
    def from_file(cls, path: Path) -> "Trace":
        from jax.profiler import ProfileData
        raw = Path(path).read_bytes()
        hlo = hlo_index(raw)
        data = ProfileData.from_serialized_xspace(raw)
        devices, host = [], []
        for plane in data.planes:
            if DEVICE_PLANE.match(plane.name):
                lines = {ln.name: ln for ln in plane.lines}
                programs = [_span(e) for e in _events(lines, PROGRAMS_LINE)]
                devices.append({"programs": programs, "ops": _ops(
                    _events(lines, OPS_LINE), programs, hlo)})
            elif plane.name == HOST_PLANE:
                for ln in plane.lines:
                    host.extend(_span(e) for e in ln.events)
        if not devices:
            raise ValueError(f"{path} holds no TPU plane")
        return cls(devices, host)

    # ---------------------------------------------------------- reading
    @property
    def window_s(self) -> float:
        return self.end - self.start

    def _in_window(self, events):
        return [e for e in events if self.start <= e.start < self.end]

    @property
    def busy_s(self) -> float:
        """Seconds of the window in which a program or an operation ran
        on the device, averaged over the chips."""
        total = 0.0
        for d in self.devices:
            total += union_s((max(e.start, self.start), min(e.end, self.end))
                             for e in d["ops"] + d["programs"]
                             if e.end > self.start and e.start < self.end)
        return total / len(self.devices)

    def scope_time_s(self, scope: str) -> float:
        """Device time of the operations under ``scope`` in the window,
        summed over the chips (control-flow operations, whose bodies are
        counted op by op, are left out)."""
        return sum(e.end - e.start for d in self.devices
                   for e in self._in_window(d["ops"])
                   if not e.container and scope in e.scopes)

    def busiest_program_runs(self) -> list[tuple[float, float]]:
        """(start, end) of each execution, in the window, of the program
        that took the most device time there, on the first chip."""
        runs = self._in_window(self.devices[0]["programs"])
        if not runs:
            return []
        time: dict[str, float] = {}
        for e in runs:
            time[e.name] = time.get(e.name, 0.0) + e.end - e.start
        top = max(time, key=time.get)
        return [(e.start, e.end) for e in runs if e.name == top]

    def runs_with_scope(self, scope: str) -> list[tuple[float, float]]:
        """(start, end) of each program execution, in the window, that ran
        an operation under ``scope``, on the first chip."""
        dev = self.devices[0]
        marks = sorted(e.start for e in dev["ops"]
                       if not e.container and scope in e.scopes)
        runs = []
        for p in sorted(self._in_window(dev["programs"]),
                        key=lambda e: e.start):
            i = bisect.bisect_left(marks, p.start)
            if i < len(marks) and marks[i] <= p.end:
                runs.append((p.start, p.end))
        return runs

    def idle_gaps(self) -> list[tuple[float, float]]:
        """Parts of the window in which the first chip ran nothing."""
        d = self.devices[0]
        return gaps([(e.start, e.end) for e in d["ops"] + d["programs"]],
                    self.start, self.end)

    def host_doing(self, start: float, end: float) -> str:
        """What the host was doing in [start, end]: the narrowest host
        span that covers at least half of it, else the one that covers
        most of it."""
        covering = []
        for e in self.host:
            cover = min(e.end, end) - max(e.start, start)
            if cover > 0 and e.name != WINDOW_SPAN:
                covering.append((cover, e.end - e.start, e.name))
        if not covering:
            return "no host span"
        half = [c for c in covering if c[0] >= (end - start) / 2]
        if half:
            return min(half, key=lambda c: c[1])[2]
        return max(covering)[2]

    def breakdown(self, top: int = 10) -> dict:
        """The device operations that took most time in the window, by
        framework path and summed over the chips (control flow left out),
        and the longest idle gaps, each named by what the host was doing
        in it."""
        by_op: dict[str, float] = {}
        for d in self.devices:
            for e in self._in_window(d["ops"]):
                if not e.container:
                    key = e.path or e.name
                    by_op[key] = by_op.get(key, 0.0) + (e.end - e.start)
        ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
        idle = sorted(self.idle_gaps(), key=lambda g: g[0] - g[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[self.host_doing(s, e), e - s]
                              for s, e in idle]}


# ------------------------------------------------------------- events
def _events(lines: dict, name: str):
    line = lines.get(name)
    return [] if line is None else line.events


def _span(e) -> Event:
    start = e.start_ns * 1e-9
    return Event(e.name, start, start + e.duration_ns * 1e-9)


def _ops(events, programs: list[Event], hlo: dict) -> list[Event]:
    """Operation events, each with its instruction's framework paths,
    looked up in the HLO of the program execution that holds it."""
    starts = [p.start for p in programs]
    out = []
    for e in events:
        start = e.start_ns * 1e-9
        name = e.name
        instr = name[1:name.find(" ")] if name.startswith("%") else name
        i = bisect.bisect_right(starts, start) - 1
        module = programs[i].name if i >= 0 else ""
        opcode, path, scopes = hlo.get(module, {}).get(instr, ("", "", ""))
        out.append(Event(instr, start, start + e.duration_ns * 1e-9, path,
                         scopes, opcode in CONTAINERS))
    return out


# ------------------------------------------------- the HLO in the trace
# Field numbers of the protos read here (tsl/profiler/protobuf/xplane.proto,
# xla/service/hlo.proto, xla/xla_data.proto).
XSPACE_PLANES = 1
XPLANE_NAME, XPLANE_EVENT_METADATA, XPLANE_STAT_METADATA = 2, 4, 5
MAP_VALUE = 2
XEVENTMETADATA_NAME, XEVENTMETADATA_STATS = 2, 5
XSTATMETADATA_ID, XSTATMETADATA_NAME = 1, 2
XSTAT_METADATA_ID, XSTAT_BYTES = 1, 6
HLOPROTO_MODULE, HLOMODULE_COMPUTATIONS = 1, 3
HLOCOMPUTATION_INSTRUCTIONS, HLOCOMPUTATION_ID = 2, 5
INSTR_NAME, INSTR_OPCODE, INSTR_METADATA, INSTR_CALLED = 1, 2, 7, 38
OPMETADATA_OP_NAME = 2


def _varint(b, i: int) -> tuple[int, int]:
    result = shift = 0
    while True:
        c = b[i]
        i += 1
        result |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return result, i


def _fields(b) -> dict[int, list]:
    """A protobuf message's fields: number -> values (ints for varints,
    memoryviews for length-delimited fields)."""
    out: dict[int, list] = {}
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(b, i)
        elif kind == 2:
            size, i = _varint(b, i)
            value, i = b[i:i + size], i + size
        elif kind == 1:
            value, i = b[i:i + 8], i + 8
        elif kind == 5:
            value, i = b[i:i + 4], i + 4
        else:
            raise ValueError(f"protobuf wire type {kind} is not read here")
        out.setdefault(key >> 3, []).append(value)
    return out


def _text(fields: dict, number: int) -> str:
    return bytes(fields.get(number, [b""])[0]).decode()


def _ids(values: list) -> list[int]:
    """A repeated int64 field, packed or not."""
    out = []
    for v in values:
        if isinstance(v, int):
            out.append(v)
        else:
            i = 0
            while i < len(v):
                x, i = _varint(v, i)
                out.append(x)
    return out


def hlo_index(raw: bytes) -> dict[str, dict[str, tuple[str, str, str]]]:
    """For each module in the trace's metadata plane, keyed by the name
    its executions carry: instruction name -> (opcode, its own framework
    path, every framework path fused into it, one per line)."""
    index = {}
    for plane in _fields(memoryview(raw)).get(XSPACE_PLANES, []):
        plane = _fields(plane)
        if _text(plane, XPLANE_NAME) != METADATA_PLANE:
            continue
        stat_names = {}
        for entry in plane.get(XPLANE_STAT_METADATA, []):
            meta = _fields(_fields(entry)[MAP_VALUE][0])
            stat_names[meta[XSTATMETADATA_ID][0]] = _text(meta,
                                                          XSTATMETADATA_NAME)
        for entry in plane.get(XPLANE_EVENT_METADATA, []):
            meta = _fields(_fields(entry)[MAP_VALUE][0])
            for stat in meta.get(XEVENTMETADATA_STATS, []):
                stat = _fields(stat)
                if stat_names.get(stat[XSTAT_METADATA_ID][0]) == "Hlo Proto":
                    index[_text(meta, XEVENTMETADATA_NAME)] = _module_index(
                        _fields(stat[XSTAT_BYTES][0])[HLOPROTO_MODULE][0])
    return index


def _module_index(module) -> dict[str, tuple[str, str, str]]:
    comps = {}
    for comp in _fields(module).get(HLOMODULE_COMPUTATIONS, []):
        comp = _fields(comp)
        instrs = []
        for ins in comp.get(HLOCOMPUTATION_INSTRUCTIONS, []):
            ins = _fields(ins)
            meta = _fields(ins[INSTR_METADATA][0]) \
                if INSTR_METADATA in ins else {}
            instrs.append((_text(ins, INSTR_NAME), _text(ins, INSTR_OPCODE),
                           _text(meta, OPMETADATA_OP_NAME),
                           _ids(ins.get(INSTR_CALLED, []))))
        comps[comp.get(HLOCOMPUTATION_ID, [0])[0]] = instrs

    memo: dict[int, frozenset] = {}

    def paths(comp_id: int) -> frozenset:
        """Framework paths of a called computation, through the fusions
        and regions it calls but not through control flow."""
        if comp_id not in memo:
            memo[comp_id] = frozenset()      # HLO calls form no cycles
            out = set()
            for _, opcode, path, called in comps.get(comp_id, []):
                if path:
                    out.add(path)
                if opcode not in CONTAINERS:
                    for c in called:
                        out |= paths(c)
            memo[comp_id] = frozenset(out)
        return memo[comp_id]

    index = {}
    for instrs in comps.values():
        for name, opcode, path, called in instrs:
            fused = {path} if path else set()
            if opcode not in CONTAINERS:
                for c in called:
                    fused |= paths(c)
            index[name] = (opcode, path, "\n".join(sorted(fused)))
    return index
