"""``rescue_scan_steps`` on flight records written by hand: the mean of the
rounds' ``rescue_steps``; ``None``, without raising, on a program whose
records lack the field (the parent of the PR that brought it) and on a
window without rounds."""

from benchmarks.context import Context
from benchmarks.layers import rescue_scan_steps


def test_mean_steps_per_round():
    rounds = [{"round": 1, "rescue_rows": 2_051, "rescue_steps": 3},
              {"round": 2, "rescue_rows": 2_048, "rescue_steps": 0},
              {"round": 3, "rescue_rows": 0, "rescue_steps": 0}]
    assert rescue_scan_steps.read(Context(rounds=rounds)) == 1.0


def test_none_where_the_record_has_no_such_field():
    assert rescue_scan_steps.read(
        Context(rounds=[{"round": 1, "pods": 2_348}])) is None
    assert rescue_scan_steps.read(Context(rounds=[])) is None
