"""Run one benchmark cell once and print its result.

Everything a cell needs is found by name from ``BENCHMARK.json``:

- the configuration file ``configs[].file`` (``bench/model_config.py``);
- the traffic mix ``bench/mixes/<traffic>.json``, whose ``kind`` names
  the generator ``bench/traffic/<kind>.py``, which also feeds the program;
- one reader per metric, ``bench/metrics/<metric>.py``;
- the limits of the cell's correctness numbers,
  ``bench/limits/<workload>.json``.

A later change adds a cell, a configuration, a mix or a metric by adding
such files and entries; this file does not change.

The traffic module (``traffic/<kind>.py``) builds the program, warms it
up, calls :meth:`Context.window_started`, measures for ``--seconds``,
reads the peak memory, frees the program's state and compares what the
timed path produced with the plain reference.  It returns a record: the
raw numbers the metric readers read, ``attempted``, ``failed`` and the
list of ``checks`` (name, value, limit).  With ``--trace 1`` it also takes a
profiler trace of part of the window, which :mod:`bench.trace_reduce`
reduces for the per-layer metrics.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import os
import shutil
import sys
import time
from typing import Any, Dict, List, Optional

from bench import model_config

TRACE_DIR = os.path.join(".cache", "bench", "trace")


class NoChip(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


@dataclasses.dataclass
class Run:
    """What a metric reader sees."""

    record: dict
    trace: Any                      # trace_reduce.Reduced or None
    shape: model_config.Shape
    peak: Any
    chips: int
    mix: dict


class Context:
    """What a traffic module gets from the harness."""

    def __init__(self, root: str, bench: dict, workload: dict, seed: int,
                 seconds: float, trace: bool, devices: list, t_start: float,
                 calibrate: bool = False, mix_override: Optional[dict] = None):
        self.workload = workload
        self.seed = int(seed)
        self.peak = None
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.devices = devices
        self.t_start = t_start
        self.conf = model_config.load(
            model_config.config_path(root, bench, workload["config"]))
        self.shape = model_config.shape_of(self.conf)
        self.mix = load_json(os.path.join(root, "bench", "mixes",
                                          workload["traffic"] + ".json"))
        self.mix.update(mix_override or {})
        #: also read the control and the planted faults (bench/calibrate.py)
        self.calibrate = calibrate
        lim_path = os.path.join(root, "bench", "limits",
                                workload["name"] + ".json")
        lim = load_json(lim_path) if os.path.exists(lim_path) else {}
        self.limits = lim.get("limits", {})
        #: training numbers with no upper reading (``PERF.md`` names each)
        self.not_compared = set(lim.get("not_compared", ()))
        self.setup_s: Optional[float] = None
        self.memory_peak_bytes: Optional[int] = None
        self.trace_path = os.path.join(root, TRACE_DIR, workload["name"])
        self._tracing = False
        self._traced = False
        #: seconds into the window at which the trace began
        self.trace_began: Optional[float] = None
        self.compiles = 0
        self._counting = False
        self._listen_compiles()

    # -- the program ---------------------------------------------------
    @property
    def cfg(self):
        return model_config.program_config(self.conf)

    def log(self, msg: str) -> None:
        print(msg, file=sys.stderr, flush=True)

    # -- phases --------------------------------------------------------
    def window_started(self) -> None:
        """Set-up ends here: everything before is ``setup_s``."""
        self.setup_s = time.perf_counter() - self.t_start
        self.compiles = 0
        self._counting = True

    def window_closed(self) -> None:
        self._counting = False
        self.stop_trace()
        self._traced = True          # nothing is traced after the close

    def _listen_compiles(self) -> None:
        import jax

        def on_event(event, duration, **kw):
            if self._counting and event.endswith("backend_compile_duration"):
                self.compiles += 1

        jax.monitoring.register_event_duration_secs_listener(on_event)

    def read_memory_peak(self) -> int:
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in self.devices]
        self.memory_peak_bytes = int(max(peaks))
        return self.memory_peak_bytes

    # -- tracing -------------------------------------------------------
    def span(self, name: str):
        """A host span in the profiler's trace (``bench.<name>``)."""
        import jax
        return jax.profiler.TraceAnnotation("bench." + name)

    def trace_at(self, elapsed: float) -> bool:
        """Called between steps with the seconds elapsed in
        the window: traces the window's last ``trace_s`` seconds (the
        mix's), so that starting and stopping the profiler stalls nothing
        before it.  Returns whether a trace is running."""
        if not self.trace or self._traced:
            return self._tracing
        length = self.mix.get("trace_s", 3.0)
        start = max(0.0, self.seconds - length)
        if not self._tracing and elapsed >= start:
            import jax
            shutil.rmtree(self.trace_path, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(self.trace_path,
                                     profiler_options=opts)
            self._tracing = True
            self.trace_began = elapsed
        return self._tracing

    def stop_trace(self) -> None:
        if self._tracing:
            import jax
            jax.profiler.stop_trace()
            self._tracing = False
            self._traced = True

    # -- correctness ---------------------------------------------------
    def check(self, name: str, value: float) -> tuple:
        """(name, value, limit) with the cell's limit for ``name``."""
        return (name, float(value), self.limits.get(name))

    def compared(self, checks: list) -> list:
        """The checks that decide ``correct``: every number but those the
        limits file lists as not compared (only a training cell's may be).
        A number with no limit stays, with limit None, so the run is not
        correct."""
        return [c for c in checks if c[0] not in self.not_compared]


def percentile(values: List[float], q: float) -> Optional[float]:
    """The ``q``-th percentile (0-100) by linear interpolation."""
    if not values:
        return None
    v = sorted(values)
    k = (len(v) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return v[lo] + (v[hi] - v[lo]) * (k - lo)


def metrics_for(bench: dict, workload: str, key: str) -> List[dict]:
    return [m for m in bench[key]
            if "workloads" not in m or workload in m["workloads"]]


def devices_for(chips: int, require_chip: bool) -> list:
    import jax
    devs = jax.devices()
    if require_chip and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX reports platform {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX reports "
                     f"{len(devs)}")
    return devs[:chips]


def run_cell(root: str, workload: str, seed: int, seconds: float,
             trace: bool, *, require_chip: bool = True,
             t_start: Optional[float] = None, peak=None,
             calibrate: bool = False,
             mix_override: Optional[dict] = None) -> dict:
    """Run one cell once; returns the result object the CLI prints.
    ``require_chip=False`` and a stand-in ``peak`` let the CPU tests
    drive everything but the look for a chip; ``calibrate`` and
    ``mix_override`` serve ``bench/calibrate.py`` and ``bench/sweep.py``."""
    t_start = time.perf_counter() if t_start is None else t_start
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    wl = cells[workload]
    devices = devices_for(wl["chips"], require_chip)

    sys.path.insert(0, os.path.join(root, "src"))
    import jax
    from repro.launch import compile_cache
    compile_cache.enable()

    ctx = Context(root, bench, wl, seed, seconds, trace, devices, t_start,
                  calibrate, mix_override)
    from bench.peaks import peak_for
    ctx.peak = peak if peak is not None else peak_for(
        devices[0].device_kind)
    mix = ctx.mix
    traffic = load_module(os.path.join(root, "bench", "traffic",
                                      mix["kind"] + ".py"),
                         "bench_traffic_" + mix["kind"])
    try:
        record = traffic.run(ctx)
    finally:
        ctx.stop_trace()
    record["setup_s"] = ctx.setup_s
    record["compiles_in_window"] = ctx.compiles

    reduced = None
    if trace:
        from bench import trace_reduce
        reduced = trace_reduce.reduce(ctx.trace_path)
        shutil.rmtree(ctx.trace_path, ignore_errors=True)

    run = Run(record=record, trace=reduced, shape=ctx.shape, peak=ctx.peak,
              chips=len(devices), mix=mix)
    metrics: Dict[str, dict] = {}
    for m in metrics_for(bench, workload,
                         "per_layer" if trace else "end_to_end"):
        reader = load_module(os.path.join(root, "bench", "metrics",
                                          m["name"] + ".py"),
                             "bench_metric_" + m["name"].replace(".", "_"))
        value = reader.read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    checks = ctx.compared(record.get("checks", []))
    correct = bool(checks) and all(
        lim is not None and val == val and val <= lim
        for _, val, lim in checks)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": ctx.memory_peak_bytes}
    out = {"correct": correct, "attempted": record["attempted"],
           "failed": record["failed"], "metrics": metrics, "device": device}
    if trace:
        if reduced is not None and reduced.devices:
            device["busy_s"] = reduced.busy_s
            device["window_s"] = reduced.window_s
            out["breakdown"] = {
                "device_ops": [[n, s] for n, s in reduced.top_ops(10)],
                "idle_gaps": [[n, s] for n, s in reduced.idle_by_label(10)]}
        else:
            device["busy_s"] = None
            device["window_s"] = None
    if calibrate:
        out["seed"] = int(seed)
        out["calibration"] = record.get("calibration")
    out["record"] = {k: v for k, v in record.items()
                     if isinstance(v, (int, float, str))}
    out["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in checks}
    return out


def cache_in_checkout(root: str) -> None:
    """JAX's persistent compilation cache at the fixed path
    ``<checkout>/.cache/jax``; the program's ``compile_cache.enable()``
    takes it from the environment.

    The cache never evicts: with a size limit (which a machine may set
    in its environment) JAX's eviction scan reads a time stamp file of
    every entry, and one entry still being written without it makes
    every later write fail, so every run compiles again."""
    path = os.path.join(root, ".cache", "jax")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    import jax
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_compilation_cache_max_size", -1)


def main(args, t_start: float) -> int:
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cache_in_checkout(root)
    try:
        out = run_cell(root, args.workload, args.seed, args.seconds,
                       bool(args.trace), t_start=t_start,
                       calibrate=bool(getattr(args, "calibrate", 0)))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(f"correct: {out['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0
