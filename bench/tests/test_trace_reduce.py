"""The trace reduction, on hand-made events and on small traces recorded
on a TPU v5e (``bench/testdata``)."""

import glob
import gzip
import os

import pytest

from bench import trace_reduce as tr
from bench.tests.tiny import REPO

MS = 1e6                                       # ns


def ev(name, start_ms, end_ms):
    return (name, start_ms * MS, end_ms * MS)


def test_intervals():
    assert tr.union([(0, 2), (1, 3), (5, 6), (6, 7)]) == [(0, 3), (5, 7)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert tr.complement([(1, 2)], (0, 4)) == [(0, 1), (2, 4)]
    assert tr.total([(0, 5), (8, 20)]) == 17


def test_self_time_and_leaves():
    evs = [ev("%while.3 = (...) while(...)", 0, 10),
           ev("%fusion.1 = f32[] fusion()", 1, 4),
           ev("%dmath_paged_decode.9 = f32[] custom-call()", 5, 9)]
    self_t, leaf = tr.self_times(evs)
    assert [t / MS for t in self_t] == [3, 3, 4]
    assert leaf == [False, True, True]
    assert tr.op_group(evs[2][0]) == "dmath_paged_decode"


def test_busy_idle_collectives_and_gap_labels():
    # one device: a while (0-10 ms) holding a matmul (0-4) and an
    # all-reduce (4-8); an async all-gather (10-14) overlapping a fusion
    # (12-16); idle 16-20 while the host samples
    ops = [ev("%while.1 = () while()", 0, 10),
           ev("%convolution.2 = bf16[] convolution()", 0, 4),
           ev("%all-reduce.3 = f32[] all-reduce()", 4, 8),
           ev("%fusion.4 = bf16[] fusion()", 12, 16)]
    aops = [ev("%all-gather-start.5 = () all-gather-start()", 10, 14)]
    host = [ev("bench.tick", 0, 10), ev("bench.tick", 10, 20),
            ev("sample", 16.5, 19.5)]
    r = tr.reduce_events({"/device:TPU:0": (ops, aops)}, host)
    assert r.window_s == pytest.approx(0.020)
    d = r.devices[0]
    # busy counts ops on the compute stream only: 0-10 and 12-16
    assert d.busy_s == pytest.approx(0.014)
    # collectives: 4-8 and 10-14 = 8 ms; compute leaves 0-4 and 12-16
    assert d.collective_s == pytest.approx(0.008)
    assert d.collective_exposed_s == pytest.approx(0.004 + 0.002)
    assert d.op_self_s["while"] == pytest.approx(0.002)
    assert d.op_count["convolution"] == 1
    # idle 10-12 (waiting on the all-gather: inside the second tick)
    # and 16-20 (the host sampling)
    assert [(pytest.approx(s), pytest.approx(n), lab)
            for s, n, lab in d.gaps] == [(0.010, 0.002, "bench.tick"),
                                         (0.016, 0.004, "sample")]
    assert r.spans["bench.tick"][0] == 2
    assert r.idle_by_label()[0] == ("sample", pytest.approx(0.004))


def test_no_bench_span_gives_nothing():
    assert tr.reduce_events({"/device:TPU:0": ([ev("x", 0, 1)], [])},
                            [ev("other", 0, 1)]) is None


RECORDED = sorted(glob.glob(os.path.join(REPO, "bench", "testdata",
                                         "*.xplane.pb.gz")))


def reduce_recorded(path, tmp_path):
    dst = tmp_path / os.path.basename(path)[:-3]
    with gzip.open(path, "rb") as src:
        dst.write_bytes(src.read())
    return tr.reduce(str(dst))


@pytest.mark.parametrize("path", RECORDED,
                         ids=[os.path.basename(p) for p in RECORDED])
def test_recorded_trace(path, tmp_path):
    """Traces recorded on a TPU v5e (qwen2-0.5b: 25 decode ticks at 8
    slots; two 1024-token train steps): device ops sit inside the
    benchmark's spans on one clock, op self times add up to busy time,
    and busy plus idle is the window."""
    r = reduce_recorded(path, tmp_path)
    assert r is not None and len(r.devices) == 1
    d = r.devices[0]
    assert 0 < d.busy_s <= r.window_s
    assert sum(d.op_self_s.values()) == pytest.approx(d.busy_s, rel=0.05)
    idle = sum(length for _, length, _ in d.gaps)
    assert d.busy_s + idle == pytest.approx(r.window_s, rel=1e-6)
    assert d.collective_s == 0.0                  # one chip
    if "serve" in path:
        assert r.spans["bench.tick"][0] == 25
        # one paged-decode call per layer per tick
        assert d.op_count["dmath_paged_decode"] == 24 * 25
        assert r.op_seconds("dmath_paged_decode")[1] > 0.01
    else:
        assert r.spans["bench.step"][0] == 2
        assert d.busy_s > 0.1                     # two 72 ms steps
