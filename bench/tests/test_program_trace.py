"""The program-span and device-program reduction, on hand-made events
and on the traces recorded on a TPU v5e (``bench/testdata``)."""

import glob
import gzip
import os

import pytest

from bench import program_trace as pt
from bench import trace_reduce as tr
from bench.tests.tiny import REPO

MS = 1e6                                       # ns


def ev(name, start_ms, end_ms):
    return (name, start_ms * MS, end_ms * MS)


def test_program_spans_count_total_and_self_time():
    # two ticks inside the benchmark's spans; the first holds a decode
    # and a sample that holds a readback; a span before the window is
    # dropped and one crossing its end is clipped
    host = [ev("bench.tick", 0, 10), ev("bench.tick", 10, 20),
            ev("repro.serve.tick", 0, 10), ev("repro.serve.decode", 1, 3),
            ev("repro.serve.sample", 4, 9), ev("repro.serve.readback", 5, 8),
            ev("repro.serve.tick", 10, 25), ev("repro.plan", -5, -1),
            ev("np.asarray", 5, 8)]
    p = pt.reduce_events({}, host)
    assert p.window == (0, 20 * MS)
    n, total, own = p.program["repro.serve.tick"]
    assert n == 2
    assert total == pytest.approx(0.020)             # 10 ms + 10 clipped
    assert own == pytest.approx(0.020 - 0.002 - 0.005)
    assert p.program["repro.serve.sample"] == pytest.approx((1, 0.005,
                                                             0.002))
    assert p.program["repro.serve.readback"] == pytest.approx((1, 0.003,
                                                               0.003))
    assert "repro.plan" not in p.program and "np.asarray" not in p.program


def test_program_spans_set_the_window_without_benchmark_spans():
    p = pt.reduce_events({}, [ev("repro.serve.tick", 2, 6),
                              ev("repro.serve.tick", 7, 9)])
    assert p.window == (2 * MS, 9 * MS)
    assert p.count("repro.serve.tick") == 2
    assert pt.reduce_events({}, [ev("other", 0, 1)]) is None


def test_modules_named_without_fingerprint_averaged_over_devices():
    mods = {"/device:TPU:0": [ev("jit_decode_step_paged(123)", 1, 5),
                              ev("jit__argmax(9)", 5, 6),
                              ev("jit_decode_step_paged(123)", 30, 40)],
            "/device:TPU:1": [ev("jit_decode_step_paged(123)", 1, 7)]}
    p = pt.reduce_events(mods, [ev("bench.tick", 0, 10)])
    # the run at 30-40 ms is outside the window
    assert p.modules["jit_decode_step_paged"] == pytest.approx((1.0, 0.005))
    assert p.modules["jit__argmax"] == pytest.approx((0.5, 0.0005))
    assert pt.module_name("jit_train_step(10796070201254671909)") == \
        "jit_train_step"


def synthetic():
    return pt.ProgramTrace(
        window=(0, 1e9),
        program={"repro.serve.tick": (4, 0.400, 0.010),
                 "repro.serve.readback": (4, 0.300, 0.300)},
        modules={"jit_decode_step_paged": (4, 0.320),
                 "jit_prefill_chunk_paged": (2, 0.040),
                 "jit__argmax": (4, 0.001)})


def test_host_ms_per_tick():
    assert pt.host_ms_per_tick(synthetic()) == pytest.approx(25.0)
    assert pt.host_ms_per_tick(pt.ProgramTrace((0, 1), {}, {})) is None


def test_programs_per_tick():
    assert pt.programs_per_tick(synthetic(), 4) == pytest.approx(2.5)
    assert pt.programs_per_tick(synthetic(), 0) is None
    assert pt.programs_per_tick(pt.ProgramTrace((0, 1), {}, {}), 4) is None


def test_decode_device_ms():
    assert pt.decode_device_ms(synthetic()) == pytest.approx(80.0)
    assert pt.decode_device_ms(pt.ProgramTrace((0, 1), {}, {})) is None


def test_prefill_device_pct():
    assert pt.prefill_device_pct(synthetic(), 0.5) == pytest.approx(8.0)
    assert pt.prefill_device_pct(synthetic(), 0.0) is None
    p = synthetic()
    del p.modules["jit_prefill_chunk_paged"]
    assert pt.prefill_device_pct(p, 0.5) is None


RECORDED = {os.path.basename(p).split(".")[0]: p for p in glob.glob(
    os.path.join(REPO, "bench", "testdata", "*.xplane.pb.gz"))}


@pytest.fixture
def recorded(tmp_path):
    def unpack(name):
        dst = tmp_path / (name + ".xplane.pb")
        with gzip.open(RECORDED[name], "rb") as src:
            dst.write_bytes(src.read())
        return str(dst)
    return unpack


def test_recorded_serve_trace_programs(recorded):
    """25 decode ticks at 8 slots: each runs the decode step and the
    three programs of the host's sampling (slice, squeeze, argmax)."""
    path = recorded("serve_decode_8slots")
    p = pt.reduce(path)
    assert p.program == {}                  # recorded before repro spans
    n, s = p.modules["jit_decode_step_paged"]
    assert n == 25 and s == pytest.approx(0.190, rel=0.01)
    assert set(p.modules) == {"jit_decode_step_paged", "jit_dynamic_slice",
                              "jit_squeeze", "jit__argmax"}
    ticks = tr.reduce(path).spans["bench.tick"][0]
    assert pt.programs_per_tick(p, ticks) == 4.0
    assert pt.decode_device_ms(p) == pytest.approx(7.6, rel=0.01)
    got = pt.numbers(path)
    assert got["ticks"] == 25 and got["programs_per_tick"] == 4.0
    assert got["serve_host_ms_per_tick"] is None
    assert got["prefill_device_pct"] is None


def test_recorded_train_trace_programs(recorded):
    p = pt.reduce(recorded("train_step_1024"))
    assert list(p.modules) == ["jit_train_step"]
    assert p.modules["jit_train_step"][0] == 2
