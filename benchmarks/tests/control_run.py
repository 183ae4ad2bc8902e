"""Run a cell with the control in the program's place.

    python3 benchmarks/tests/control_run.py --workload <cell> --seed <n> --seconds <s> --trace 0

Same arguments as ``benchmarks/run.py`` (add ``--cpu-dry-run`` off the
chip).  The cell's deployment keeps its data, its books and its checks; its
``solve`` is answered by ``reference.control.propose_without_accept`` and
what it "holds" is the control's own accounting, so the one thing that can
fail is the guarantee the control breaks.  The last line must read
``"correct": false``.
"""

from __future__ import annotations

import importlib
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


#: every deployment module a configuration can name
DEPLOYMENTS = ("served_socket", "gang_quota_inprocess")


def control_of(base):
    from benchmarks.reference import checks, control

    class Control(base):
        def path_ok(self, path, want):
            return True     # the control has no paths to take

        def __init__(self, *args, **kw):
            super().__init__(*args, **kw)
            self._rounds: list[dict] = []

        def warm_up(self, params, plan=None):
            # the control compiles nothing; keep the fill the cell starts at
            self.first_round_s = 0.0
            if self.standing():
                self.offer(self.standing() + self.wave(), counts=False)
                self.solve()

        def solve(self) -> int:
            books = self.books
            names = sorted(books.pending)
            requested, _ = checks.requested_by_node(
                len(books.node_names), books.node_row, books.requests,
                books.bound, books.dims)
            rows = control.propose_without_accept(
                self.rng, books.alloc, requested,
                np.stack([books.requests[p] for p in names]))
            doc = {"assignments": {p: books.node_names[r]
                                   for p, r in zip(names, rows) if r >= 0},
                   "failures": {p: "no candidate fits"
                                for p, r in zip(names, rows) if r < 0}}
            self._rounds.append({
                "round": len(self._rounds) + 1, "solver": "batch",
                "solve_path": "control", "pods": len(names),
                "placed": len(doc["assignments"]),
                "failed": len(doc["failures"]), "duration_s": 0.0,
                "solve_device_s": 0.0, "phase_s": {}})
            return books.record_round(doc)

        @property
        def round_seq(self) -> int:
            return len(self._rounds)

        def flight_records(self, after_round: int) -> list[dict]:
            return self._rounds[after_round:]

        def held(self) -> dict:
            books = self.books
            requested, _ = checks.requested_by_node(
                len(books.node_names), books.node_row, books.requests,
                books.bound, books.dims)
            by_name = lambda a: dict(zip(books.node_names, a))  # noqa: E731
            return {"alloc": by_name(books.alloc),
                    "usage": by_name(books.usage),
                    "requested": by_name(requested),
                    "pending": set(books.pending), "bound": dict(books.bound),
                    "bound_requests": {p: books.requests[p]
                                       for p in books.bound}}

    return Control


def main(argv: list[str] | None = None) -> int:
    from benchmarks import run

    for name in DEPLOYMENTS:
        module = importlib.import_module(f"benchmarks.deployments.{name}")
        module.Deployment = control_of(module.Deployment)
    return run.main(argv)


if __name__ == "__main__":
    sys.exit(main())
