#!/usr/bin/env python3
"""The program's own spans and the device's programs in a profiler trace.

:mod:`bench.trace_reduce` reads a trace's device ops and the benchmark's
``bench.*`` spans.  The same trace holds two more things, which this
module reads:

- program spans: every span of ``repro.obs`` is a ``repro.<name>``
  annotation on the host, telemetry on or off.  :func:`reduce` gives each
  such span on the driving thread, clipped to the window, its count,
  total seconds and self seconds (the total less the time its nested
  ``repro.*`` spans cover);
- device programs: each device plane's ``XLA Modules`` line has one
  event per program run (``jit_decode_step_paged(<fingerprint>)``).
  :func:`reduce` gives each program, named without the fingerprint, its
  count and device seconds inside the window, averaged over devices.

The window is the benchmark spans' extent, as in :mod:`bench.trace_reduce`,
or the program spans' own where the trace holds no benchmark span.  Below
the reduction are the serve loop's numbers taken from it (see ``PERF.md``,
section 3).  From the root of a checkout::

    python3 bench/program_trace.py <trace dir or .xplane.pb file>

prints the reduction and those numbers as one JSON object.
"""

from __future__ import annotations

import dataclasses
import json
import os
import re
import sys
from typing import Dict, List, Optional, Sequence, Tuple

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from bench import trace_reduce as tr  # noqa: E402

Event = tr.Event
PROGRAM_PREFIX = "repro."
TICK = "repro.serve.tick"
READBACK = "repro.serve.readback"
DECODE = "jit_decode_step_paged"
PREFILL = "jit_prefill_chunk_paged"


@dataclasses.dataclass
class ProgramTrace:
    window: tr.Interval                           # ns
    program: Dict[str, Tuple[int, float, float]]  # n, total s, self s
    modules: Dict[str, Tuple[float, float]]       # n, device s

    def count(self, span: str) -> int:
        return self.program.get(span, (0, 0.0, 0.0))[0]


def module_name(name: str) -> str:
    """``jit_decode_step_paged(1569...)`` -> ``jit_decode_step_paged``."""
    return re.sub(r"\(\d+\)$", "", name)


def clip(events: Sequence[Event], window: tr.Interval) -> List[Event]:
    return [(n, max(s, window[0]), min(e, window[1])) for n, s, e in events
            if e > window[0] and s < window[1]]


def reduce_events(modules: Dict[str, Sequence[Event]],
                  host: Sequence[Event]) -> Optional[ProgramTrace]:
    """The reduction of already-extracted events: ``modules`` maps a
    device name to its ``XLA Modules`` events, ``host`` is the driving
    thread's spans.  None when the trace holds neither a benchmark nor a
    program span."""
    marks = [ev for ev in host if ev[0].startswith(tr.SPAN_PREFIX)] or \
        [ev for ev in host if ev[0].startswith(PROGRAM_PREFIX)]
    if not marks:
        return None
    window = (min(s for _, s, _ in marks), max(e for _, _, e in marks))
    spans = clip([ev for ev in host if ev[0].startswith(PROGRAM_PREFIX)],
                 window)
    self_ns, _ = tr.self_times(spans)
    program: Dict[str, Tuple[int, float, float]] = {}
    for (n, s, e), own in zip(spans, self_ns):
        c, t, o = program.get(n, (0, 0.0, 0.0))
        program[n] = (c + 1, t + (e - s) / 1e9, o + own / 1e9)
    per_dev = max(1, len(modules))
    mods: Dict[str, Tuple[float, float]] = {}
    for evs in modules.values():
        for n, s, e in clip(evs, window):
            c, t = mods.get(module_name(n), (0.0, 0.0))
            mods[module_name(n)] = (c + 1 / per_dev,
                                    t + (e - s) / 1e9 / per_dev)
    return ProgramTrace(window=window, program=program, modules=mods)


def extract(path: str) -> Tuple[Dict[str, List[Event]], List[Event]]:
    """(``XLA Modules`` events per device, driving-thread spans) of the
    trace file at ``path``.  The driving thread is the one holding the
    benchmark's spans, else the one holding the program's."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    modules: Dict[str, List[Event]] = {}
    lines: List[List[Event]] = []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CUSTOM" not in plane.name:
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules.setdefault(plane.name, []).extend(
                        (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                        for ev in line.events)
        elif plane.name.startswith("/host:"):
            lines.extend([(ev.name, ev.start_ns,
                           ev.start_ns + ev.duration_ns)
                          for ev in line.events] for line in plane.lines)
    for prefix in (tr.SPAN_PREFIX, PROGRAM_PREFIX):
        driving = [evs for evs in lines
                   if any(n.startswith(prefix) for n, _, _ in evs)]
        if driving:
            return modules, [ev for evs in driving for ev in evs]
    return modules, []


def reduce(path_or_dir: str) -> Optional[ProgramTrace]:
    path = path_or_dir
    if os.path.isdir(path_or_dir):
        path = tr.find_xplane(path_or_dir)
        if path is None:
            return None
    return reduce_events(*extract(path))


# ---------------------------------------------------------------------------
# the serve loop's numbers; each is None where its span or program is absent
# ---------------------------------------------------------------------------

def host_ms_per_tick(p: ProgramTrace) -> Optional[float]:
    """Host time of a tick less its wait on the device's tokens, in ms:
    (total ``repro.serve.tick`` - total ``repro.serve.readback``) / ticks."""
    n = p.count(TICK)
    if not n:
        return None
    readback = p.program.get(READBACK, (0, 0.0, 0.0))[1]
    return (p.program[TICK][1] - readback) / n * 1e3


def programs_per_tick(p: ProgramTrace, ticks: int) -> Optional[float]:
    """Device programs run in the window per tick (``ticks`` of them)."""
    if not ticks or not p.modules:
        return None
    return sum(c for c, _ in p.modules.values()) / ticks


def decode_device_ms(p: ProgramTrace) -> Optional[float]:
    """Device ms of one ``jit_decode_step_paged`` run."""
    if DECODE not in p.modules:
        return None
    c, s = p.modules[DECODE]
    return s / c * 1e3


def prefill_device_pct(p: ProgramTrace, busy_s: float) -> Optional[float]:
    """``jit_prefill_chunk_paged``'s device seconds over the device's busy
    seconds, in %."""
    if PREFILL not in p.modules or not busy_s:
        return None
    return 100.0 * p.modules[PREFILL][1] / busy_s


def numbers(path: str) -> dict:
    """The reduction of the trace at ``path`` and the serve loop's numbers.
    Ticks are the program's ``repro.serve.tick`` spans, or the benchmark's
    ``bench.tick`` in a trace of a build that writes none."""
    p = reduce(path)
    if p is None:
        return {}
    r = tr.reduce(path)
    busy = r.busy_s if r is not None and r.devices else 0.0
    ticks = p.count(TICK) or (
        r.spans.get(tr.SPAN_PREFIX + "tick", (0, 0.0))[0] if r else 0)
    return {"window_s": (p.window[1] - p.window[0]) / 1e9,
            "busy_s": busy, "ticks": ticks,
            "program": p.program, "modules": p.modules,
            "serve_host_ms_per_tick": host_ms_per_tick(p),
            "programs_per_tick": programs_per_tick(p, ticks),
            "decode_device_ms": decode_device_ms(p),
            "prefill_device_pct": prefill_device_pct(p, busy)}


if __name__ == "__main__":
    if len(sys.argv) != 2:
        sys.exit(__doc__)
    print(json.dumps(numbers(sys.argv[1])))
