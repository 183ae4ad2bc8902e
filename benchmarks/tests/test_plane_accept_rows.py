"""``plane_accept_rows`` on flight records written by hand: the mean of the
rounds' ``accept_rows``; ``None``, without raising, on a program whose
records lack the field (the parent of the PR that brought it) and on a
window without rounds."""

from benchmarks.context import Context
from benchmarks.layers import plane_accept_rows


def test_mean_rows_per_round():
    rounds = [{"round": 1, "accept_rows": 4_096},
              {"round": 2, "accept_rows": 2_048},
              {"round": 3, "accept_rows": 0}]
    assert plane_accept_rows.read(Context(rounds=rounds)) == 2_048.0


def test_none_where_the_record_has_no_such_field():
    assert plane_accept_rows.read(
        Context(rounds=[{"round": 1, "pods": 3_456}])) is None
    assert plane_accept_rows.read(Context(rounds=[])) is None
