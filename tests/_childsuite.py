"""Shared launcher for the fake-multi-device child pytest suites.

Several test modules re-exec themselves in a child process with
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (the parent pytest
process must keep the real single-device topology).  Historically each
parent test ran its child with a blocking ``subprocess.run``, serializing
~2.5 minutes of child compiles behind the parent's own tests.  Here the
children are *launched at collection time* (``conftest.py``) and only
*joined* when their parent test executes, so child compile time overlaps
the serial parent tests — the main lever that brought the default tier-1
run under two minutes on a 2-core container.

Output goes to temp files (a filled stdout PIPE would deadlock a chatty
child); ``join`` returns (returncode, combined tail).
"""

from __future__ import annotations

import atexit
import os
import subprocess
import sys
import tempfile
from typing import Dict, Optional, Tuple

_TESTS_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_TESTS_DIR, "..", "src")

#: test-file basename -> (child-marker env var, fake device count, the
#: parent test that joins the child).  The env var doubles as the in-child
#: guard: when it is already set we ARE the child and must not recurse.
#: Launches are gated on the JOINING test being selected (conftest.py), so
#: `-k` filters and --collect-only never spawn a child nobody waits for.
SUITES: Dict[str, Tuple[str, int, str]] = {
    "test_pipeline.py":
        ("REPRO_PIPE_FAKE_DEVICES", 8, "test_pipeline_suite_subprocess"),
    "test_core_gemm.py":
        ("REPRO_FAKE_DEVICES", 8, "test_gemm_suite_subprocess"),
    "test_gemm_conformance.py":
        ("REPRO_GEMM_CONF_DEVICES", 8, "test_gemm_conformance_subprocess"),
    "test_primitives.py":
        ("REPRO_PRIM_CHILD", 8, "test_primitives_subprocess"),
    "test_redistribute_dtype.py":
        ("REPRO_REDIST_CHILD", 8, "test_redistribute_dtype_subprocess"),
    "test_memory_model.py":
        ("REPRO_MEM_FAKE_DEVICES", 8, "test_memory_model_suite_subprocess"),
    "test_api_session.py":
        ("REPRO_API_FAKE_DEVICES", 8, "test_api_session_subprocess"),
    "test_fused_kernels.py":
        ("REPRO_FUSED_CHILD", 4, "test_fused_kernels_subprocess"),
}

_JOIN_TO_SUITE = {join: base for base, (_v, _n, join) in SUITES.items()}

#: Production-mesh dry-run cells (test_dryrun_contract.py) — CLI children
#: under the same overlap-and-join discipline.
DRYRUN_CELLS = [
    ("qwen2-0.5b", "decode_32k", False),
    ("mamba2-780m", "long_500k", True),
]
_dryrun_outdirs: Dict[str, str] = {}

_procs: Dict[str, subprocess.Popen] = {}
_outfiles: Dict[str, str] = {}

#: Persistent XLA compilation cache, keyed PER TEST CELL (the ROADMAP
#: tier-1 wall-time lever): each child suite / dry-run cell gets its own
#: directory under the base so concurrent children never contend on the
#: same entries, and a re-run loads the executables the last one wrote.
#: A caller's JAX_COMPILATION_CACHE_DIR wins over the per-cell path (every
#: process then shares the caller's directory).
#:
#: REPRO_XLA_CACHE_DIR=<dir> moves the per-cell base; =off disables it.
_XLA_CACHE_BASE = os.environ.get(
    "REPRO_XLA_CACHE_DIR",
    os.path.join(_TESTS_DIR, "..", ".cache", "xla"))


def _caller_cache_dir() -> Optional[str]:
    """The caller's JAX_COMPILATION_CACHE_DIR; a per-cell directory this
    module handed to a parent process (and its xdist workers) is not."""
    d = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    base = os.path.abspath(_XLA_CACHE_BASE)
    if d and os.path.commonpath([os.path.abspath(d), base]) != base:
        return d
    return None


def compile_cache_env(cell: str) -> Dict[str, str]:
    """Env vars enabling the per-cell persistent compilation cache."""
    if _XLA_CACHE_BASE == "off" or _caller_cache_dir():
        return {}
    d = os.path.join(os.path.abspath(_XLA_CACHE_BASE), cell)
    try:
        os.makedirs(d, exist_ok=True)
    except OSError:
        return {}
    return {"JAX_COMPILATION_CACHE_DIR": d}


@atexit.register
def _reap():
    """Don't leave orphan children if the parent session dies early."""
    for p in _procs.values():
        if p.poll() is None:
            p.kill()


def in_any_child() -> bool:
    return any(os.environ.get(var) == str(n)
               for var, n, _join in SUITES.values())


def launch_for_item(item_name: str, markexpr: Optional[str] = None) -> None:
    """Start whatever child the named (selected) parent test will join."""
    base = _JOIN_TO_SUITE.get(item_name)
    if base is not None:
        launch(base, markexpr=markexpr)
    elif item_name.startswith("test_dryrun_cell_compiles"):
        # parametrized id carries "arch-shape-multi": launch only that cell
        launch_dryrun_cells(only=item_name)


def launch_dryrun_cells(only: Optional[str] = None) -> None:
    """Start the dry-run CLI cells (idempotent); joined via join_cmd.

    ``only`` restricts the launch to cells whose "arch-shape" appears in
    the string (a parametrized test id), so a ``-k``-filtered run never
    spawns the deselected cell's multi-minute compile.
    """
    for arch, shape, multi in DRYRUN_CELLS:
        key = f"dryrun_{arch}_{shape}"
        if key in _procs or (only is not None
                             and f"{arch}-{shape}" not in only):
            continue
        _dryrun_outdirs[key] = tempfile.mkdtemp(prefix=key + "_")
        env = dict(os.environ)
        env.update(compile_cache_env(key))
        env["PYTHONPATH"] = os.pathsep.join(
            [_SRC] + env.get("PYTHONPATH", "").split(os.pathsep))
        cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
               "--shape", shape, "--out", _dryrun_outdirs[key]]
        if multi:
            cmd.append("--multi-pod")
        launch_cmd(key, cmd, env=env, cwd=os.path.join(_TESTS_DIR, ".."))


def dryrun_outdir(key: str) -> str:
    return _dryrun_outdirs[key]


def launch(basename: str, markexpr: Optional[str] = None) -> None:
    """Start the child suite for ``basename`` if not already running."""
    if basename in _procs or basename not in SUITES:
        return
    var, devs, _join = SUITES[basename]
    if os.environ.get(var) == str(devs):      # we ARE that child
        return
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + f" --xla_force_host_platform_device_count={devs}")
    env[var] = str(devs)
    env.update(compile_cache_env(var.lower()))
    env["PYTHONPATH"] = os.pathsep.join(
        [_SRC] + env.get("PYTHONPATH", "").split(os.pathsep))
    cmd = [sys.executable, "-m", "pytest", "-q", "-x",
           os.path.join(_TESTS_DIR, basename)]
    if markexpr:
        # forward the parent's -m so CI's "slow or not slow" reaches the
        # child battery too (pyproject addopts would otherwise deselect)
        cmd += ["-m", markexpr]
    launch_cmd(basename, cmd, env=env)


def join(basename: str, timeout: int = 900) -> Tuple[int, str]:
    """Wait for the child suite; returns (returncode, output tail)."""
    if basename not in _procs:                # standalone / direct run
        launch(basename)
    return _join_proc(basename, timeout)


def launch_cmd(key: str, cmd, env=None, cwd=None) -> None:
    """Start an arbitrary child command (e.g. a dry-run CLI cell) under the
    same overlap-and-join discipline as the pytest child suites."""
    if key in _procs:
        return
    out = tempfile.NamedTemporaryFile(mode="w", suffix=f"_{key}.log",
                                      delete=False)
    _outfiles[key] = out.name
    _procs[key] = subprocess.Popen(list(cmd), env=env, cwd=cwd, stdout=out,
                                   stderr=subprocess.STDOUT, text=True)
    out.close()


def join_cmd(key: str, timeout: int = 900) -> Tuple[int, str]:
    if key not in _procs:
        raise KeyError(f"child command {key!r} was never launched")
    return _join_proc(key, timeout)


def _join_proc(key: str, timeout: int) -> Tuple[int, str]:
    p = _procs[key]
    timed_out = False
    try:
        p.wait(timeout=timeout)
    except subprocess.TimeoutExpired:
        timed_out = True
        p.kill()
        p.wait()
    try:
        with open(_outfiles[key]) as f:
            out = f.read()
    except OSError:
        out = ""
    finally:
        try:
            os.unlink(_outfiles[key])
        except OSError:
            pass
    if timed_out:
        return 124, (f"child {key} timed out after {timeout}s; "
                     f"output so far:\n" + out[-8000:])
    return p.returncode, out[-8000:]
