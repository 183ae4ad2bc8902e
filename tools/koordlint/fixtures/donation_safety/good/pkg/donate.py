"""Seeded known-GOOD corpus for donation-safety: the intended idioms —
one fresh buffer per pytree field, immediate rebind of the donated
name, metadata reads after donation, reads before the call, one donating
call returned from each arm of a choice."""
import jax
import jax.numpy as jnp
from flax import struct


@struct.dataclass
class State:
    alloc: jax.Array
    used: jax.Array
    usage: jax.Array

    @classmethod
    def zeros(cls, n):
        def z():
            return jnp.zeros((n, 4), jnp.int32)

        return cls(alloc=z(), used=z(), usage=z())  # one buffer per field


def _solve(state, batch):
    return state


solve = jax.jit(_solve, donate_argnums=(0,))
solve_wide = jax.jit(_solve, donate_argnums=(0,))


class Scheduler:
    def __init__(self, state, batch):
        self.state = state
        self.batch = batch

    def round(self):
        before = self.state + 0           # ok: read BEFORE the donation
        self.state = solve(self.state, self.batch)  # ok: rebind idiom
        n = self.state.shape[0]           # ok: reads the NEW buffer
        return before, n

    def either_program(self, state, wide):
        # ok: each arm returns its donating call, so the second arm's
        # read of `state` never follows the first arm's donation
        if wide:
            return solve_wide(state, self.batch)
        return solve(state, self.batch)

    def rebind_local(self):
        state = self.state
        cap = state.shape                 # ok: metadata before
        state = solve(state, self.batch)  # ok: tuple-free rebind
        return state, cap


class Pipeline:
    """Double-buffered round pipeline (ISSUE 11), the blessed swap:
    the dispatch rebinds ``self.state`` to the donating call's result
    IN the call statement, so between the halves every reader sees the
    in-flight (live) buffer and the dead one is unreachable; the host
    half blocks on the handle's arrays, never the pre-dispatch state."""

    def __init__(self, state, batch):
        self.state = state
        self.batch = batch
        self.inflight = None

    def dispatch(self):
        self.state = solve(self.state, self.batch)  # the blessed swap
        self.inflight = self.state    # ok: references the NEW buffer
        return self.inflight

    def commit(self):
        done = self.inflight          # ok: the live in-flight result
        self.inflight = None
        return done
