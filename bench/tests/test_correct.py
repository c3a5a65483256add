"""`correct` comes out false when the timed path is broken underneath.

Each test drives the rest of a run of a cell, with the harness's look for a
chip skipped, at a size a test run holds on the CPU (a few twins, the jnp
path of the kernels), with the cell's own limits.  The control, the
reference at 'high' (three bfloat16 passes) in the program's place, and each
fault the cells can have must fail at least one compared number; the sound
program must pass them all.  One chip has no exchange between chips to leave
out.  A second family, the forced Van der Pol oscillator of the physics file
beside this test, runs through the same harness with no edit to it.
"""
from pathlib import Path

import numpy as np
import pytest

from bench.harness import Cell, physics_for, run_cell
from repro.core.fleet import FleetMerinda
from repro.twin.monitor import DivergenceGuard
from repro.twin.scenario import ScenarioRunner

SEED = 2 ** 31 + 17


def small(name: str) -> Cell:
    cell = Cell.load(name)
    c = cell.config
    c["twins"] = 8
    c["server_config"]["refit_slots"] = 4
    c["merinda"]["use_pallas"] = False
    c["max_loop_rate_per_s"] = 150
    return cell


def van_der_pol() -> Cell:
    """`online64.steady` with the Van der Pol oscillator in the F-8's place:
    its physics, its sampling interval and a gain for each of its states."""
    cell = small("online64.steady")
    cell.config["system"] = "van_der_pol"
    cell.config["merinda"]["dt"] = 0.02
    cell.traffic["sas_gains"] = [0.0, -0.1]
    cell.physics = physics_for(cell.config,
                               Path(__file__).parent / "van_der_pol.json")
    return cell


def run(cell: str | Cell, **kw) -> dict:
    cell = small(cell) if isinstance(cell, str) else cell
    return run_cell(cell, SEED, 1.5, False, require_chip=False,
                    log=lambda s: None, **kw)


def failing(result) -> list[str]:
    return [k for k, c in result["compared"].items()
            if not c["value"] <= c["limit"]]


@pytest.mark.parametrize("name", ["online64.steady", "online64.whatif"])
def test_sound_program_is_correct(name):
    result = run(name)
    assert result["correct"], result["compared"]


@pytest.mark.parametrize("name", ["online64.steady", "online64.whatif"])
def test_control_is_not_correct(name):
    result = run(name, stand_in="high")
    assert not result["correct"], result["compared"]


def test_step_that_returns_its_state_unchanged(monkeypatch):
    orig = FleetMerinda.train_step_per_slot

    def frozen(self, state, y_win, u_win):
        _, loss, ok = orig(self, state, y_win, u_win)
        return state, loss, ok
    monkeypatch.setattr(FleetMerinda, "train_step_per_slot", frozen)
    result = run("online64.steady")
    assert "step" in failing(result), result["compared"]


def test_half_the_batch_left_out(monkeypatch):
    orig = FleetMerinda.train_step_per_slot

    def half(self, state, y_win, u_win):
        S = y_win.shape[1] // 2
        return orig(self, state, y_win[:, :S], u_win[:, :S])
    monkeypatch.setattr(FleetMerinda, "train_step_per_slot", half)
    result = run("online64.steady")
    assert {"loss", "step"} & set(failing(result)), result["compared"]


def test_guard_answer_altered_where_produced(monkeypatch):
    orig = DivergenceGuard.score

    def shifted(self, theta, ys, us):
        return np.roll(np.asarray(orig(self, theta, ys, us)), 1)
    monkeypatch.setattr(DivergenceGuard, "score", shifted)
    result = run("online64.steady")
    assert "guard" in failing(result), result["compared"]


def test_scenario_answer_altered_where_produced(monkeypatch):
    orig = ScenarioRunner.rollout

    def nudged(self, theta_hist, count, y0, us):
        center, lo, hi, conf = orig(self, theta_hist, count, y0, us)
        center = center.copy()
        center[:, -1] += 1e-3
        return center, lo, hi, conf
    monkeypatch.setattr(ScenarioRunner, "rollout", nudged)
    result = run("online64.whatif")
    assert "scenario" in failing(result), result["compared"]


def test_second_family_sound_program_is_correct():
    result = run(van_der_pol())
    assert result["compared"]["ring"]["value"] == 0.0, result["compared"]
    assert result["correct"], result["compared"]


def test_second_family_step_that_returns_its_state_unchanged(monkeypatch):
    orig = FleetMerinda.train_step_per_slot

    def frozen(self, state, y_win, u_win):
        _, loss, ok = orig(self, state, y_win, u_win)
        return state, loss, ok
    monkeypatch.setattr(FleetMerinda, "train_step_per_slot", frozen)
    result = run(van_der_pol())
    assert "step" in failing(result), result["compared"]
