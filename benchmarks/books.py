"""What a run keeps as it goes: every pod it offered, every answer it got.

The books are the client's view.  After the window closes they are all the
reference needs to recompute the guarantees from nothing but what was sent
and what came back, and they are compared cell by cell with what the
scheduler holds.
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks.reference import checks


class Books:
    def __init__(self, dims: int):
        self.dims = dims
        self.node_names: list[str] = []
        self.node_row: dict[str, int] = {}
        self.alloc = np.zeros((0, dims), np.int32)
        self.usage = np.zeros((0, dims), np.int32)
        self.requests: dict[str, np.ndarray] = {}
        self.pending: set[str] = set()
        self.bound: dict[str, str] = {}
        #: pods offered since the window opened (the standing pods that fit
        #: no node are never offered) and those of them bound in it
        self.window_open = False
        self.offered: set[str] = set()
        self.bound_in_window = 0
        #: client-side stamps for the tail: frame issued -> response held
        self.sent_at: dict[str, float] = {}
        self.bound_at: dict[str, float] = {}
        #: answers that broke the protocol, counted as they come
        self.stray_binds = 0
        self.undiagnosed = 0

    def set_nodes(self, names: list[str], alloc: np.ndarray,
                  usage: np.ndarray) -> None:
        self.node_names = list(names)
        self.node_row = {name: i for i, name in enumerate(names)}
        self.alloc = alloc.copy()
        self.usage = usage.copy()

    def offer(self, name: str, request: np.ndarray, counts: bool = True,
              stamp: bool = False) -> None:
        self.requests[name] = request
        self.pending.add(name)
        if self.window_open and counts:
            self.offered.add(name)
        if stamp:
            self.sent_at[name] = time.perf_counter()

    def leave(self, name: str) -> None:
        del self.bound[name]

    def withdraw(self, name: str) -> None:
        """A pending pod is taken back before any answer bound it."""
        self.pending.remove(name)
        del self.requests[name]

    def record_round(self, doc: dict, scope: set[str] | None = None) -> int:
        """Fold one solve response in; returns how many pods it bound.
        ``scope``: the pods the answering scheduler was offered, where the
        books hold more than one scheduler's."""
        now = time.perf_counter()
        for pod, node in doc["assignments"].items():
            if pod not in self.pending:
                self.stray_binds += 1
                continue
            self.pending.discard(pod)
            self.bound[pod] = node
            if pod in self.offered:
                self.bound_in_window += 1
                self.bound_at[pod] = now
        expected = self.pending if scope is None else self.pending & scope
        self.undiagnosed += len(expected - doc["failures"].keys())
        return len(doc["assignments"])

    # -- after the window ---------------------------------------------------

    def verify(self, held: dict) -> dict[str, int]:
        """The numbers compared, all with limit 0.  ``held`` is what the
        scheduler holds now: ``alloc``/``usage``/``requested`` as
        {node name: row vector}, ``pending`` names, ``bound`` {pod: node},
        ``bound_requests`` {pod: vector}."""
        n = len(self.node_names)
        requested, unknown = checks.requested_by_node(
            n, self.node_row, self.requests, self.bound, self.dims)

        def rows(by_name: dict) -> np.ndarray:
            out = np.full((n, self.dims), -1, np.int64)
            for name, vec in by_name.items():
                if name in self.node_row:
                    out[self.node_row[name]] = vec
            return out

        sent_requests = (np.stack([self.requests[p] for p in self.bound])
                         if self.bound else np.zeros((0, self.dims)))
        held_requests = (np.stack([held["bound_requests"].get(
            p, np.full(self.dims, -1)) for p in self.bound])
            if self.bound else np.zeros((0, self.dims)))
        return {
            "overcommit_cells": checks.overcommit_cells(self.alloc,
                                                        requested),
            "unknown_node_binds": unknown,
            "stray_binds": self.stray_binds,
            "undiagnosed_pods": self.undiagnosed,
            # an assignment returned is an assignment charged
            "charge_mismatch_cells": checks.mismatch_cells(
                rows(held["requested"]), requested),
            # what was sent is what the scheduler holds
            "held_node_mismatch_cells": (
                checks.mismatch_cells(rows(held["alloc"]), self.alloc)
                + checks.mismatch_cells(rows(held["usage"]), self.usage)
                + abs(len(held["alloc"]) - n)),
            "held_pod_mismatch": (
                len(self.pending ^ set(held["pending"]))
                + len(self.bound.items() ^ held["bound"].items())
                + checks.mismatch_cells(held_requests, sent_requests)),
        }
