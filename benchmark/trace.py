"""Reduction of a JAX profiler trace (`.xplane.pb`) to numbers, with
`jax.profiler.ProfileData` only.

What the planes look like on a TPU v5e (read by hand, PR 24, PERF.md section 5):
one plane `/device:TPU:<n>` per chip. Its line `XLA Ops` holds one event per
executed HLO instruction, named by the instruction's whole text
(`%fusion.295 = (f32[65024,4544]{...}, ...) fusion(...), kind=kLoop, ...`),
with start and duration in ns; a `while` spans its body. A Pallas kernel is a
`custom-call` whose target is `tpu_custom_call`, named after the jitted
function round the `pallas_call` (`%_flash_attention.26 = ... custom-call(`).
Zero-length `custom-call`s with target `AllocateBuffer` are not kernels. The
lines `Steps` and `XLA Modules` hold one event per executed program (three a
training step: two one-microsecond helpers and the step), and `Async XLA Ops`
the copies that overlap. The plane `/host:CPU` has one line per thread;
`python` is the main thread and holds the `jax.profiler.TraceAnnotation`s.
Host and device events are on one clock.

On a backend with no device plane (the CPU rehearsal) the host's events that
carry an `hlo_op` stat stand in, and `Trace.kind` says so: such numbers are
for testing the arithmetic and are never reported as device metrics.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

Event = Tuple[str, float, float]        # name, start s, duration s

_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")
# control flow that contains other operations: no time of its own
_CONTAINERS = ("while", "conditional", "call")


def parse_op(text: str) -> Tuple[str, str, str]:
    """(name, opcode, result shape) of an `XLA Ops` event's text:
    `%name = shape opcode(operands), attributes`. Operand names are not
    looked at: `%custom-call.27` as an operand makes no kernel. Text that
    is not an instruction (the CPU's plain op names) is its own name, with
    the opcode guessed from it."""
    head, sep, rest = text.partition(" = ")
    if not sep:
        name = text.lstrip("%")
        return name, re.sub(r"[.\d]+$", "", name), ""
    name = head.lstrip("%")
    depth, i = 0, 0
    while i < len(rest):                 # the shape may be a tuple
        c = rest[i]
        depth += c in "([{"
        depth -= c in ")]}"
        if c == " " and depth == 0:
            break
        i += 1
    shape, tail = rest[:i], rest[i + 1:]
    return name, tail.partition("(")[0].strip(), shape


def is_collective(text: str) -> bool:
    return parse_op(text)[1].startswith(COLLECTIVES)


def is_pallas_kernel(text: str) -> bool:
    return (parse_op(text)[1] == "custom-call"
            and 'custom_call_target="tpu_custom_call"' in text)


def short_name(text: str) -> str:
    """`fusion.295 fusion (f32[65024,4544], ...`: enough to find the
    instruction in the compiled text, short enough for a result line."""
    name, opcode, shape = parse_op(text)
    shape = re.sub(r"\{[^}]*\}", "", shape)
    return f"{name} {opcode} {shape[:60]}".strip()


@dataclasses.dataclass
class Trace:
    kind: str                            # "tpu" | "host-xla"
    window_s: float                      # first traced event to the last
    ops: Dict[int, List[Event]]          # per device
    spans: List[Event]                   # host spans (all threads)
    _self: Dict[int, Dict[str, float]] = dataclasses.field(
        default_factory=dict, repr=False)    # self_seconds, once per device

    # -- device busy / idle ------------------------------------------
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over devices."""
        if not self.ops:
            return 0.0
        return sum(sum(e - s for s, e in _union(ev))
                   for ev in self.ops.values()) / len(self.ops)

    def self_seconds(self, device: Optional[int] = None) -> Dict[str, float]:
        """Per operation name, the time no nested operation covers."""
        dev = min(self.ops) if device is None else device
        if dev not in self._self:
            self._self[dev] = _self_times(self.ops.get(dev, []))
        return self._self[dev]

    def seconds_where(self, pred, device: Optional[int] = None) -> float:
        """Self time of the operations whose event text satisfies `pred`."""
        return sum(t for n, t in self.self_seconds(device).items() if pred(n))

    def top_ops(self, n: int = 10) -> List[List]:
        st = self.self_seconds()
        return [[short_name(k), st[k]]
                for k in sorted(st, key=st.get, reverse=True)[:n]]

    def idle_gaps(self, n: int = 10, min_s: float = 50e-6) -> List[List]:
        """The idle time of the first device, by what the host was doing:
        each gap between operations goes to the host span of the
        benchmark's own (`bench/...`) that overlaps it most, else to
        "unattributed"."""
        dev = min(self.ops) if self.ops else None
        if dev is None:
            return []
        busy = _union(self.ops[dev])
        own = [s for s in self.spans if s[0].startswith("bench/")]
        by: Dict[str, float] = {}
        for (s0, e0), (s1, _) in zip(busy, busy[1:]):
            gap = s1 - e0
            if gap < min_s:
                continue
            best, cover = "unattributed", 0.0
            for name, ss, sd in own:
                ov = min(s1, ss + sd) - max(e0, ss)
                if ov > cover:
                    best, cover = name, ov
            by[best] = by.get(best, 0.0) + gap
        return [[k, by[k]] for k in sorted(by, key=by.get, reverse=True)[:n]]

    def span_count(self, name: str) -> int:
        return sum(1 for s in self.spans if s[0] == name)


def _union(events: List[Event]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for _, s, d in sorted(events, key=lambda e: e[1]):
        if d <= 0:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], s + d))
        else:
            out.append((s, s + d))
    return out


def _self_times(events: List[Event]) -> Dict[str, float]:
    """Operations on one device's line nest (a `while` spans its body).
    Self time = duration minus the direct children's durations."""
    out: Dict[str, float] = {}
    stack: List[List] = []               # [name, end, self]

    def close(upto: float):
        while stack and stack[-1][1] <= upto:
            name, _, self_t = stack.pop()
            if parse_op(name)[1] not in _CONTAINERS:
                out[name] = out.get(name, 0.0) + max(self_t, 0.0)

    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        if d <= 0:
            continue
        close(s)
        if stack:
            stack[-1][2] -= d
        stack.append([name, s + d, d])
    close(float("inf"))
    return out


def find_xplane(trace_dir: str) -> Optional[str]:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    ops: Dict[int, List[Event]] = {}
    spans: List[Event] = []
    host_xla: List[Event] = []
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            lines = [ln for ln in plane.lines if ln.name == OPS_LINE]
            ops[int(m.group(1))] = [
                (e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                for ln in lines for e in ln.events]
        elif plane.name == "/host:CPU":
            for ln in plane.lines:
                for e in ln.events:
                    ev = (e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                    if e.duration_ns > 0 and any(k == "hlo_op"
                                                 for k, _ in e.stats):
                        host_xla.append(ev)
                    elif e.duration_ns > 0:
                        spans.append(ev)
    kind = "tpu"
    if not ops and host_xla:
        kind, ops = "host-xla", {0: host_xla}
    every = [e for ev in ops.values() for e in ev]
    window = (max(s + d for _, s, d in every) - min(s for _, s, _ in every)
              if every else 0.0)
    return Trace(kind=kind, window_s=window, ops=ops, spans=spans)


def describe(path: str, per_line: int = 8) -> str:
    """Planes, lines and the first events of each: for reading a trace by
    hand before trusting the reduction."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"PLANE {plane.name}")
        for ln in plane.lines:
            evs = list(ln.events)
            out.append(f"  LINE {ln.name!r}: {len(evs)} events")
            for e in evs[:per_line]:
                out.append(f"    {e.name[:90]!r} start={e.start_ns:.0f}ns "
                           f"dur={e.duration_ns:.0f}ns")
    return "\n".join(out)
