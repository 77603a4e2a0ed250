"""The out-of-core training cell at smoke size on the CPU: a sound run is
correct, the bf16-state control and the half-batch reference put in the
program's place come out not correct, and faults planted in the trainer
make the run not correct."""

import pytest

import cells

CELL = "smollm-135m.train_ooc"


def test_cell_runs_correct_and_controls_fail():
    out = cells.run(CELL, seconds=0.5)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"step_s", "setup_s"}
    assert out["attempted"] >= 4
    bf16 = cells.run(CELL, seconds=0.3, options={"control": "bf16_state"})
    assert not bf16["correct"], bf16["checks"]
    change = bf16["checks"]["change_gap"]
    assert change["value"] > change["limit"]
    half = cells.run(CELL, seconds=0.3, options={"control": "half_batch"})
    assert not half["correct"], half["checks"]


def test_unknown_control_is_refused():
    with pytest.raises(ValueError, match="no control"):
        cells.run(CELL, seconds=0.3, options={"control": "fp8"})


def test_traced_run_reads_trainer_metrics():
    out = cells.run(CELL, seconds=0.5, trace=True)
    assert out["correct"]
    assert {"ooc.sweep_s", "pager.faults_per_step",
            "device_idle.train"} <= set(out["metrics"])


def _state_unchanged(ooc, monkeypatch):
    monkeypatch.setattr(ooc.OOCTrainer, "_apply_pending", lambda self: None)


def _half_batch(ooc, monkeypatch):
    orig = ooc.OOCTrainer._prepare_update

    def half(self, batch):
        return orig(self, {k: v[:len(v) // 2] for k, v in batch.items()})
    monkeypatch.setattr(ooc.OOCTrainer, "_prepare_update", half)


def _loss_altered(ooc, monkeypatch):
    orig = ooc.OOCTrainer._prepare_update

    def altered(self, batch):
        orig(self, batch)
        self._pending["metrics"]["loss"] *= 1.01
    monkeypatch.setattr(ooc.OOCTrainer, "_prepare_update", altered)


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch, _loss_altered])
def test_fault_in_trainer_is_not_correct(fault, monkeypatch):
    from repro.train import ooc
    fault(ooc, monkeypatch)
    out = cells.run(CELL, seconds=0.3)
    assert not out["correct"], out["checks"]
