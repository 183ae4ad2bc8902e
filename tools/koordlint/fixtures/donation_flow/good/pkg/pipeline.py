"""Donation-disciplined twins of the bad corpus (must-pass)."""

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
        self.kit = SolverKit()
        self.snapshot = snapshot

    def dispatch(self, batch):
        # the blessed swap: re-point the snapshot at the in-flight
        # result before anything can read the dead buffers
        a, new_state = self.kit.pass1(self.snapshot.state, batch)
        self.snapshot.state = new_state
        return a

    def round(self, batch):
        a = self.dispatch(batch)
        return self.commit(a)

    def commit(self, a):
        # legal: dispatch() swapped before returning
        return self.snapshot.state, a

    def metadata_survives(self, batch):
        a, new_state = self.kit.pass1(self.snapshot.state, batch)
        rows = self.snapshot.state.shape  # metadata outlives donation
        self.snapshot.state = new_state
        return a, rows

    def swap_through_method(self, batch):
        # the swap may live inside the owning object's method
        # (Scheduler._reservation_prepass adopts through the snapshot)
        a, new_state = self.kit.pass1(self.snapshot.state, batch)
        self.snapshot.adopt_state(new_state)
        return self.snapshot.state, a

    def rebind_idiom(self, state, batch):
        # `x = f(x, ...)`: the donated name is dead and immediately
        # rebound to the result — the intended idiom
        batch2, state = self.kit.pass1(state, batch)
        return state, batch2

    def rebound_alias_is_fresh(self, batch, fresh):
        # a local that once aliased self.snapshot but was REBOUND to a
        # different object before the read: its attrs are not the dead
        # path (the alias map must drop the binding at the rebind)
        snap = self.snapshot
        a, new_state = self.kit.pass1(self.snapshot.state, batch)
        snap = fresh
        scratch = snap.state
        self.snapshot.state = new_state
        return a, scratch
