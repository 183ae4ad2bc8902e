"""Seeded donation-flow violations (must-flag corpus).

The ISSUE-11 double-buffer hand-off, done wrong three ways: a dispatch
that never performs the blessed swap (an interprocedural kill every
caller inherits), a host half that reads the dead state through two
call hops, and the stash-the-donated-buffer tenancy anti-idiom (the
pre-dispatch stash points at the consumed buffer even after the swap).
"""

import jax


def _pass1_impl(state, batch):
    return batch, state


class SolverKit:
    def __init__(self):
        self._pass1_one = jax.jit(_pass1_impl, donate_argnums=(0,))
        self._pass1_sh = jax.jit(_pass1_impl, donate_argnums=(0,))
        self.sharded = False

    def pass1(self, state, batch):
        # one entry per stage: the kit picks the program, and either
        # one donates the caller's ``state``
        if self.sharded:
            return self._pass1_sh(state, batch)
        return self._pass1_one(state, batch)


class Pipeline:
    def __init__(self, snapshot):
        # the typed kit attribute: a call of ``self.kit.pass1`` donates
        # through the entry's donating-parameter summary
        self.kit = SolverKit()
        self.snapshot = snapshot

    def dispatch_without_swap(self, batch):
        # BAD: donates snapshot.state and never re-points it — the
        # buffer is dead at exit and every caller inherits ⊥
        a, _ = self.kit.pass1(self.snapshot.state, batch)
        return a

    def round(self, batch):
        a = self.dispatch_without_swap(batch)
        # BAD: commit() reads the state the dispatch left dead
        return self.commit(a)

    def commit(self, a):
        return self.snapshot.state, a

    def stash_the_buffer(self, batch):
        # BAD (the tenancy anti-idiom): the pre-dispatch stash keeps
        # pointing at the consumed buffer even after the blessed swap
        old = self.snapshot.state
        a, new_state = self.kit.pass1(self.snapshot.state, batch)
        self.snapshot.state = new_state
        return old.mean(), a

    def swap_through_rebound_alias(self, batch, fresh):
        # BAD: `snap` was REBOUND to a different object before the
        # store, so `snap.state = ...` is NOT the blessed swap — the
        # real self.snapshot.state stays dead at the read
        snap = self.snapshot
        a, new_state = self.kit.pass1(self.snapshot.state, batch)
        snap = fresh
        snap.state = new_state
        return self.snapshot.state, a
