"""The rest of a run with the timed path broken underneath: the harness's
look for a chip is skipped (``--rehearse``), everything else is the run's own
code, and ``correct`` has to come out false for each fault a cell can have."""

import json

import pytest

from benchmark import data as bdata, faults, run as brun


def _run(capsys, workload, seed=2_147_483_777, trace=0):
    rc = brun.main(["--workload", workload, "--seed", str(seed), "--seconds", "0.5",
                    "--trace", str(trace), "--rehearse"])
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0 and out, rc
    return json.loads(out[-1])


@pytest.fixture(autouse=True)
def small(monkeypatch):
    monkeypatch.setattr(brun, "REHEARSE_ROWS", 24_000)
    monkeypatch.setattr(bdata, "BLOCK_ROWS", 6_000)


def failing(line):
    return sorted(n for n, c in line["checks"].items() if c["value"] > c["limit"])


@pytest.mark.parametrize("workload", ["criteo67.fit-eval", "higgs.fit"])
def test_sound_run_is_correct(capsys, workload):
    line = _run(capsys, workload)
    assert line["correct"] is True and line["failed"] == 0, failing(line)
    assert line["attempted"] >= 1
    assert list(line)[-1] == "checks"
    assert line["device"]["platform"] == "cpu"  # a rehearsal says what it ran on


def test_traced_rehearsal_prints_no_device_metric(capsys):
    line = _run(capsys, "criteo67.fit-eval", trace=1)
    assert line["correct"] is True
    assert "busy_s" not in line["device"]
    for name in ("device_idle_share", "train_step_mfu"):
        assert name not in line["metrics"]
    assert "iter_wall_p50_ms" in line["metrics"]


def test_fault_state_left_unchanged(capsys):
    with faults.state_unchanged():
        line = _run(capsys, "criteo67.fit-eval")
    assert line["correct"] is False
    assert "leaf_value_rms_gap" in failing(line)
    # the repeated tree is a tenth of a step off everywhere: hundreds of times a sound reading
    assert line["checks"]["leaf_value_rms_gap"]["value"] > 0.05


def test_fault_half_the_rows_left_out(capsys):
    with faults.half_rows():
        line = _run(capsys, "criteo67.fit-eval")
    assert line["correct"] is False
    assert "count_mismatch" in failing(line)
    assert line["checks"]["count_mismatch"]["value"] >= 24_000 // 2


@pytest.mark.parametrize("workload", ["criteo67.fit-eval", "higgs.fit"])
def test_fault_answer_altered_where_produced(capsys, workload):
    with faults.answer_altered():
        line = _run(capsys, workload)
    assert line["correct"] is False
    assert "leaf_value_rms_gap" in failing(line)


def test_wrong_program_is_not_reported_as_correct(capsys, monkeypatch):
    """A latched degradation makes the run incorrect and every iteration failed."""
    from lightgbm_tpu.boosting.gbdt import Booster

    monkeypatch.setattr(Booster, "degraded", property(lambda self: True))
    line = _run(capsys, "higgs.fit")
    assert line["correct"] is False and line["failed"] == line["attempted"]
    assert failing(line) == ["degraded"]


def test_no_chip_no_number(capsys):
    rc = brun.main(["--workload", "higgs.fit", "--seed", "1", "--seconds", "1",
                    "--trace", "0"])
    assert rc != 0
    assert capsys.readouterr().out.strip() == ""
