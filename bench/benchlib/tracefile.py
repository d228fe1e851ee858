"""Reduction of a JAX profiler trace to device busy, idle and collective
time.

``read`` takes the ``.xplane.pb`` that ``jax.profiler`` writes and keeps
two things: the op events of each TPU (the ``XLA Ops`` line of each
``/device:TPU:<n>`` plane) and the benchmark's own host spans (events
named ``bench.*`` on the host plane).  Everything after that is interval
arithmetic on nanoseconds, kept free of the profiler so the tests can
drive it with hand-made intervals.
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
COLLECTIVE = re.compile(
    r"(all-gather|all-reduce|collective-permute|reduce-scatter|all-to-all|"
    r"collective-broadcast)(-start|-done)?$")
_OP = re.compile(r"%?([^\s=]+) = (\S+)")


@dataclasses.dataclass
class Event:
    name: str           # on a device: the HLO instruction's text
    start: float        # ns
    end: float          # ns
    leaf: bool = True   # no other op of its line runs inside it
    inner: float = 0.0  # ns of the ops directly inside it

    @property
    def dur(self) -> float:
        return self.end - self.start

    @property
    def op(self) -> str:
        """The instruction's name: ``%fusion.12 = f32[..] fusion(..)``
        -> ``fusion.12``."""
        m = _OP.match(self.name)
        return m.group(1) if m else self.name

    @property
    def opcode(self) -> str:
        """The HLO opcode: ``%psum.9 = f32[8] all-reduce(..)`` ->
        ``all-reduce`` (names need not say what an op is)."""
        head, sep, rest = self.name.partition(" = ")
        if not sep:
            return self.name
        depth, i = 0, 0
        for i, c in enumerate(rest):      # skip the (possibly tuple) type
            depth += (c == "(") - (c == ")")
            if depth == 0 and c == " ":
                break
        return rest[i + 1:].split("(", 1)[0].strip()

    @property
    def label(self) -> str:
        """Name and result shape, without layouts: ``fusion.12
        f32[2,7800,1536]``."""
        m = _OP.match(self.name)
        if not m:
            return self.name
        shape = re.sub(r"\{[^{}]*\}", "", m.group(2))
        return f"{m.group(1)} {shape[:80]}"


@dataclasses.dataclass
class Trace:
    devices: Dict[int, List[Event]]
    host: List[Event]

    def window(self) -> Interval:
        spans = [e for e in self.host if e.name == WINDOW_SPAN]
        if len(spans) != 1:
            raise ValueError(f"expected one {WINDOW_SPAN} span, found "
                             f"{len(spans)}")
        return spans[0].start, spans[0].end


def read(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[int, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        name = plane.name
        if name.startswith(DEVICE_PREFIX):
            tail = name[len(DEVICE_PREFIX):]
            if not tail.isdigit():
                continue
            ops = devices.setdefault(int(tail), [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend(Event(e.name, e.start_ns, e.end_ns)
                               for e in line.events)
            nest(ops)
        elif name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        host.append(Event(e.name, e.start_ns, e.end_ns))
    return Trace(devices, host)


def nest(ops: List[Event]) -> None:
    """Mark the ops that hold others (a ``while`` holds its body's ops on
    the same line) and how long their direct children run."""
    ops.sort(key=lambda e: (e.start, -e.end))
    stack: List[Event] = []
    for e in ops:
        while stack and e.end > stack[-1].end:
            stack.pop()
        if stack:
            stack[-1].leaf = False
            stack[-1].inner += e.dur
        stack.append(e)


# ----------------------------------------------------- interval algebra
def merge(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def length(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in merge(intervals))


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> float:
    """Length of the union of ``a`` outside the union of ``b``."""
    a, b = merge(a), merge(b)
    out, j = 0.0, 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out += e - cur
    return out


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The idle intervals of ``[lo, hi]`` between merged busy intervals."""
    out, cur = [], lo
    for s, e in merge(clip(busy, lo, hi)):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if cur < hi:
        out.append((cur, hi))
    return out


# ------------------------------------------------------------ summaries
def device_summary(ops: Sequence[Event], lo: float, hi: float) -> dict:
    """Busy, idle and collective-exposed time of one device in [lo, hi]
    (seconds), and the self time of its ops by label (an op's time less
    that of the ops inside it).  A collective is exposed where no other
    innermost op runs beside it."""
    spans = clip([(e.start, e.end) for e in ops], lo, hi)
    coll = clip([(e.start, e.end) for e in ops
                 if COLLECTIVE.match(e.opcode)], lo, hi)
    comp = clip([(e.start, e.end) for e in ops
                 if e.leaf and not COLLECTIVE.match(e.opcode)], lo, hi)
    by_op: Dict[str, float] = defaultdict(float)
    for e in ops:
        if e.start >= lo and e.end <= hi:
            by_op[e.label] += (e.dur - e.inner) * 1e-9
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": length(spans) * 1e-9,
        "collective_s": length(coll) * 1e-9,
        "collective_exposed_s": subtract(coll, comp) * 1e-9,
        "by_op": dict(by_op),
    }


def name_gap(gap: Interval, host: Sequence[Event]) -> str:
    """The host span that covers most of ``gap`` (the innermost on a tie),
    or ``host`` when none does."""
    best = max(((min(e.end, gap[1]) - max(e.start, gap[0]), -e.dur, e.name)
                for e in host if e.name != WINDOW_SPAN), default=None)
    return best[2] if best is not None and best[0] > 0 else "host"


def idle_gaps(ops: Sequence[Event], host: Sequence[Event], lo: float,
              hi: float, top: int = 10) -> List[Tuple[str, float]]:
    """The longest idle gaps of one device in [lo, hi], each named by the
    host span it falls in: ``[(name, seconds), ...]``."""
    out = [(name_gap(g, host), (g[1] - g[0]) * 1e-9)
           for g in gaps([(e.start, e.end) for e in ops], lo, hi)]
    return sorted(out, key=lambda x: -x[1])[:top]


def top_ops(by_op: Dict[str, float], top: int = 10
            ) -> List[Tuple[str, float]]:
    return sorted(by_op.items(), key=lambda x: -x[1])[:top]


def summarize(trace: Trace, device_ids: Sequence[int],
              window: Optional[Interval] = None) -> dict:
    """Per-device summaries over the benchmark's window span, their mean
    busy time, and the breakdown the result line carries (top ops and
    idle gaps of the first device)."""
    lo, hi = window or trace.window()
    per = {i: device_summary(trace.devices.get(i, []), lo, hi)
           for i in device_ids}
    first = device_ids[0]
    return {
        "window_s": (hi - lo) * 1e-9,
        "devices": per,
        "busy_s": sum(p["busy_s"] for p in per.values()) / len(per),
        "breakdown": {
            "device_ops": [list(x) for x in top_ops(per[first]["by_op"])],
            "idle_gaps": [list(x) for x in idle_gaps(
                trace.devices.get(first, []), trace.host, lo, hi)],
        },
    }
