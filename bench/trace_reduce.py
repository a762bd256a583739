"""Reduce a profiler trace (``.xplane.pb``) to what the metrics read.

A trace taken with ``jax.profiler`` holds one plane per device
(``/device:TPU:<n>``) whose ``XLA Ops`` line has an event per HLO op
executed (a ``while`` holds the ops of its body, so events nest), an
``Async XLA Ops`` line with the start-to-done spans of asynchronous ops,
and a host plane (``/host:CPU``) with the threads' spans, among them the
benchmark's own ``bench.*`` spans on the thread that drives the program.

:func:`reduce` gives, per device and inside the window the benchmark's
spans cover:

- busy intervals: the union of the intervals of the ops on the compute
  stream (``XLA Ops``); a device that only waits on an asynchronous copy
  or collective is idle;
- time per op: each op's self time (its span less the ops nested in it),
  grouped by the op's name without its numeric suffix;
- collectives: all-reduce, reduce-scatter, all-gather, collective-permute
  and all-to-all spans, and the part of them during which no other op
  (a leaf op that is not a collective) runs on that device;
- idle gaps: the complement of busy, each labelled by the innermost host
  span on the driving thread that covers the gap's middle.

Times are in seconds.  The functions below the reader work on plain
``(name, start_ns, end_ns)`` tuples so that they can be tested alone.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]          # name, start ns, end ns
Interval = Tuple[float, float]

COLLECTIVE = re.compile(
    r"all-reduce|reduce-scatter|all-gather|collective-permute|all-to-all"
    r"|allreduce|reducescatter|allgather")
SPAN_PREFIX = "bench."


@dataclasses.dataclass
class Device:
    name: str
    busy_s: float
    op_self_s: Dict[str, float]
    op_count: Dict[str, int]
    collective_s: float
    collective_exposed_s: float
    gaps: List[Tuple[float, float, str]]      # (start s, length s, label)


@dataclasses.dataclass
class Reduced:
    window: Interval                           # ns, from the bench spans
    devices: List[Device]
    spans: Dict[str, Tuple[int, float]]        # bench span -> (count, s)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    @property
    def busy_s(self) -> float:
        return sum(d.busy_s for d in self.devices) / max(1, len(self.devices))

    def op_seconds(self, pattern: str) -> Tuple[int, float]:
        """(count of devices, summed self seconds per device on average)
        of ops whose grouped name contains ``pattern``."""
        tot = [sum(v for k, v in d.op_self_s.items() if pattern in k)
               for d in self.devices]
        return len(tot), sum(tot) / max(1, len(tot))

    def top_ops(self, n: int = 10) -> List[Tuple[str, float]]:
        agg: Dict[str, float] = {}
        for d in self.devices:
            for k, v in d.op_self_s.items():
                agg[k] = agg.get(k, 0.0) + v / len(self.devices)
        return sorted(agg.items(), key=lambda kv: -kv[1])[:n]

    def idle_by_label(self, n: int = 10) -> List[Tuple[str, float]]:
        agg: Dict[str, float] = {}
        for d in self.devices:
            for _, length, label in d.gaps:
                agg[label] = agg.get(label, 0.0) + length / len(self.devices)
        return sorted(agg.items(), key=lambda kv: -kv[1])[:n]


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------

def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals: Sequence[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Parts of the (unioned) intervals ``a`` not covered by ``b``."""
    b = union(b)
    out: List[Interval] = []
    j = 0
    for s, e in union(a):
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def complement(busy: Sequence[Interval], window: Interval) -> List[Interval]:
    return subtract([window], busy)


# ---------------------------------------------------------------------------
# nesting: self time and leaves
# ---------------------------------------------------------------------------

def op_group(name: str) -> str:
    """``%fusion.477 = (bf16[...]) fusion(...)`` -> ``fusion``."""
    head = name.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"(\.\d+)+$", "", head)


def self_times(events: Sequence[Event]) -> Tuple[List[float], List[bool]]:
    """Self time (ns) of each event and whether it is a leaf, where an
    event nests inside an earlier one that contains it."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    self_t = [e[2] - e[1] for e in events]
    leaf = [True] * len(events)
    stack: List[int] = []
    for i in order:
        s, e = events[i][1], events[i][2]
        while stack and events[stack[-1]][2] <= s:
            stack.pop()
        if stack and e <= events[stack[-1]][2]:
            p = stack[-1]
            self_t[p] -= e - s
            leaf[p] = False
        stack.append(i)
    return self_t, leaf


def reduce_device(name: str, ops: Sequence[Event], async_ops: Sequence[Event],
                  window: Interval, host: Sequence[Event]) -> Device:
    ops = [(n, max(s, window[0]), min(e, window[1])) for n, s, e in ops
           if e > window[0] and s < window[1]]
    async_ops = [(n, max(s, window[0]), min(e, window[1]))
                 for n, s, e in async_ops if e > window[0] and s < window[1]]
    busy = union([(s, e) for _, s, e in ops])
    st, leaf = self_times(ops)
    per_op: Dict[str, float] = {}
    count: Dict[str, int] = {}
    for (n, _, _), t in zip(ops, st):
        g = op_group(n)
        per_op[g] = per_op.get(g, 0.0) + t / 1e9
        count[g] = count.get(g, 0) + 1
    coll = [(s, e) for n, s, e in list(ops) + list(async_ops)
            if COLLECTIVE.search(op_group(n))]
    compute = [(s, e) for (n, s, e), lf in zip(ops, leaf)
               if lf and not COLLECTIVE.search(op_group(n))]
    exposed = subtract(coll, compute)
    gaps = [(s / 1e9, (e - s) / 1e9, label_at(host, (s + e) / 2))
            for s, e in complement(busy, window)]
    return Device(name=name, busy_s=total(busy) / 1e9, op_self_s=per_op,
                  op_count=count,
                  collective_s=total(union(coll)) / 1e9,
                  collective_exposed_s=total(exposed) / 1e9, gaps=gaps)


def label_at(host: Sequence[Event], t: float) -> str:
    """Name of the shortest host span covering time ``t``."""
    best: Optional[Event] = None
    for ev in host:
        if ev[1] <= t <= ev[2] and (best is None
                                    or ev[2] - ev[1] < best[2] - best[1]):
            best = ev
    return best[0] if best is not None else "no host span"


def reduce_events(devices: Dict[str, Tuple[List[Event], List[Event]]],
                  host: Sequence[Event]) -> Optional[Reduced]:
    """The reduction of already-extracted events: ``devices`` maps a
    device name to its (ops, async ops), ``host`` is the driving thread's
    spans.  None when the trace holds no benchmark span."""
    spans = [ev for ev in host if ev[0].startswith(SPAN_PREFIX)]
    if not spans:
        return None
    window = (min(s for _, s, _ in spans), max(e for _, _, e in spans))
    counts: Dict[str, Tuple[int, float]] = {}
    for n, s, e in spans:
        c, t = counts.get(n, (0, 0.0))
        counts[n] = (c + 1, t + (e - s) / 1e9)
    devs = [reduce_device(name, ops, aops, window, host)
            for name, (ops, aops) in sorted(devices.items())]
    return Reduced(window=window, devices=devs, spans=counts)


# ---------------------------------------------------------------------------
# reading the file
# ---------------------------------------------------------------------------

def find_xplane(trace_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return paths[-1] if paths else None


def extract(path: str):
    """(devices, host) events of the trace file at ``path``."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    devices: Dict[str, Tuple[List[Event], List[Event]]] = {}
    host_lines: List[List[Event]] = []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CUSTOM" not in plane.name:
            ops: List[Event] = []
            aops: List[Event] = []
            for line in plane.lines:
                if line.name not in ("XLA Ops", "Async XLA Ops"):
                    continue
                dst = ops if line.name == "XLA Ops" else aops
                dst.extend((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                           for ev in line.events)
            if ops or aops:
                devices[plane.name] = (ops, aops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                       for ev in line.events]
                if any(n.startswith(SPAN_PREFIX) for n, _, _ in evs):
                    host_lines.append(evs)
    host = [ev for evs in host_lines for ev in evs]
    return devices, host


def reduce(path_or_dir: str) -> Optional[Reduced]:
    path = path_or_dir
    if os.path.isdir(path_or_dir):
        path = find_xplane(path_or_dir)
        if path is None:
            return None
    devices, host = extract(path)
    return reduce_events(devices, host)
