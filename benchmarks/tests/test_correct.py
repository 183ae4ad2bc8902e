"""``correct`` can come out false: the control, and the timed path broken.

Each case drives the whole of a run (``benchmarks/run.py``'s ``main``, minus
its look for a chip: ``--cpu-dry-run`` at the configuration's ``dry``
sizes) and reads ``correct`` off the last line.

    python3 -m pytest benchmarks/tests -q
"""

from __future__ import annotations

import importlib
import json
import os

import pytest

from benchmarks import run
from benchmarks.tests import control_run

with open(os.path.join(run.ROOT, "BENCHMARK.json")) as _f:
    _WORKLOADS = json.load(_f)["workloads"]
CELLS = [w["name"] for w in _WORKLOADS]
MESH_CELLS = [w["name"] for w in _WORKLOADS if w["chips"] > 1]


def last_line(capsys, main, workload: str) -> dict:
    rc = main(["--workload", workload, "--seed", "11", "--seconds", "3",
               "--trace", "0", "--cpu-dry-run"])
    assert rc == 0
    line = capsys.readouterr().out.strip().splitlines()[-1]
    assert line.startswith("DRY_RUN {"), line
    return json.loads(line[len("DRY_RUN "):])


def failing(result: dict) -> set[str]:
    return {k for k, v in result["compared"].items()
            if v["value"] != v["limit"]}


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(capsys, workload):
    result = last_line(capsys, run.main, workload)
    assert result["correct"] is True, failing(result)
    assert result["metrics"]["placed_share"]["value"] <= 100.0


@pytest.mark.parametrize("workload", CELLS)
def test_control_is_not_correct(capsys, monkeypatch, workload):
    for name in control_run.DEPLOYMENTS:
        module = importlib.import_module(f"benchmarks.deployments.{name}")
        monkeypatch.setattr(module, "Deployment",
                            control_run.control_of(module.Deployment))
    result = last_line(capsys, run.main, workload)
    assert result["correct"] is False
    assert "overcommit_cells" in failing(result)


def state_unchanged(real):
    """The step answers, and leaves the scheduler's state as it was."""
    def schedule_round(self):
        result = real(self)
        for pod in list(result.assignments):
            self.delete_pod(pod)
        return result
    return schedule_round


def answer_altered(real):
    """One answer names another node than the one that was charged."""
    def schedule_round(self):
        result = real(self)
        nodes = sorted(set(result.assignments.values()))
        for pod, node in list(result.assignments.items())[:1]:
            result.assignments[pod] = next(n for n in nodes if n != node)
        return result
    return schedule_round


def half_left_out(real):
    """Half of the queue never reaches the solve."""
    def _active_pods(self):
        return real(self)[::2]
    return _active_pods


FAULTS = {
    "state_unchanged": ("schedule_round", state_unchanged,
                        "charge_mismatch_cells"),
    "answer_altered": ("schedule_round", answer_altered,
                       "charge_mismatch_cells"),
    "half_left_out": ("_active_pods", half_left_out, "undiagnosed_pods"),
}


@pytest.mark.parametrize("fault", FAULTS)
@pytest.mark.parametrize("workload", CELLS)
def test_broken_timed_path_is_not_correct(capsys, monkeypatch, workload,
                                          fault):
    from koordinator_tpu.scheduler.scheduler import Scheduler

    method, wrap, number = FAULTS[fault]
    monkeypatch.setattr(Scheduler, method, wrap(getattr(Scheduler, method)))
    result = last_line(capsys, run.main, workload)
    assert result["correct"] is False
    assert number in failing(result)


@pytest.mark.parametrize("workload", MESH_CELLS)
def test_exchange_between_chips_left_out_is_not_correct(capsys, monkeypatch,
                                                        workload):
    """``psum`` answers with the shard's own part.  The run must not come
    out correct; a run that dies (the round's second pass, which the
    missing exchange sets off, crashes the program: PERF.md section 7)
    prints no result line, which is not correct either."""
    import jax

    from koordinator_tpu.parallel import sharded

    if len(jax.devices()) < 4:
        pytest.skip("needs four (virtual) devices: benchmarks/tests/conftest.py")

    def forget_programs():
        for entry in vars(sharded).values():
            if hasattr(entry, "cache_clear"):
                entry.cache_clear()

    forget_programs()
    monkeypatch.setattr(jax.lax, "psum", lambda x, axis_name, **kw: x)
    try:
        try:
            result = last_line(capsys, run.main, workload)
        except Exception:  # noqa: BLE001 - any death of the run is a failed run
            return
        assert result["correct"] is False
    finally:
        forget_programs()
