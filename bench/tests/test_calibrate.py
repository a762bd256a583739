"""Limits are set from readings by the stated rule."""

import json
import os

import pytest

from bench.calibrate import NoUpperReading, limits_from


def rows(program, calibration):
    return [{"seed": i, "checks": {n: {"value": v, "limit": None}
                                   for n, v in p.items()},
             "calibration": calibration} for i, p in enumerate(program)]


def test_limit_between_readings_closer_to_the_upper():
    r = rows([{"loss": 1e-4}, {"loss": 2e-4}],
             {"control": {"loss": 0.0216}})
    e = limits_from(r, training=True)["loss"]
    assert (e["lower"], e["upper"]) == (2e-4, 0.0216)
    assert e["limit"] == pytest.approx(2e-4 ** (1 / 3) * 0.0216 ** (2 / 3))
    assert e["lower"] < e["limit"] < e["upper"]


def test_faults_and_unchanged_state_set_the_upper():
    r = rows([{"grad": 0.01, "change": 0.002}],
             {"control": {"grad": 0.02, "change": 0.001},
              "half_batch": {"grad": 0.5, "change": 0.01}})
    got = limits_from(r, training=True)
    # the control reads under 3x lower for both: the half batch (>= 10x)
    # and the unchanged state (1) give the upper readings
    assert got["grad"]["upper"] == 0.5
    assert got["change"]["upper"] == 1.0


@pytest.mark.parametrize("training", [True, False], ids=["train", "serve"])
def test_no_upper_reading(training):
    """Only a training number that reads small may go uncompared; a serve
    number with no upper reading is an error."""
    r = rows([{"served_gap": 0.03, "unserved": 0.0}],
             {"control": {"served_gap": 0.05}})
    if training:
        got = limits_from(r, training=True)
        assert got["served_gap"]["limit"] is None
        assert got["unserved"]["limit"] == 0.0
    else:
        with pytest.raises(NoUpperReading):
            limits_from(r, training=False)


def test_large_training_number_with_no_upper_reading_is_an_error():
    r = rows([{"loss": 0.2}], {"control": {"loss": 0.3}})
    with pytest.raises(NoUpperReading):
        limits_from(r, training=True)


def test_serve_number_with_no_limit_keeps_the_run_incorrect(tmp_path):
    """A limits file that names no limit for a serve number leaves that
    number compared with limit None: the run is not correct."""
    from bench.tests import tiny
    root = tiny.make_root(tmp_path, [("tiny-qwen2", "tiny-longdecode")])
    path = os.path.join(root, "bench", "limits",
                        "tiny-qwen2.tiny-longdecode.json")
    with open(path, "w") as fh:
        json.dump({"limits": {"unserved": 0.0}}, fh)
    out = tiny.run(root, "tiny-qwen2.tiny-longdecode", seconds=1.0)
    assert out["checks"]["served_gap"]["limit"] is None
    assert out["correct"] is False


def test_rows_from_earlier_output(tmp_path):
    """``--from`` takes the rows from result lines of ``run.py`` (with
    or without ``--calibrate 1``) and from this tool's own lines, and
    skips the rest."""
    from bench.calibrate import rows_from
    row = {"seed": 7, "checks": {"loss": {"value": 1e-4, "limit": None}},
           "calibration": {"control": {"loss": 0.02}}}
    result = dict(row, correct=False, metrics={}, attempted=1)
    path = tmp_path / "runs.out"
    path.write_text("check loss: 1e-4 limit None\n{\"correct\": true}\n"
                    + json.dumps(result) + "\n" + json.dumps(row) + "\n")
    plain = tmp_path / "seta_3000000021.out"
    plain.write_text(json.dumps({"correct": False, "checks": {
        "loss": {"value": 3e-4, "limit": None}}}) + "\n")
    got = rows_from([str(path), str(plain)])
    assert got[:2] == [row, row]
    assert got[2] == {"seed": 3000000021, "calibration": {},
                      "checks": {"loss": {"value": 3e-4, "limit": None}}}
    assert limits_from(got, training=True)["loss"]["lower"] == 3e-4
    assert limits_from(got, training=True)["loss"]["upper"] == 0.02
