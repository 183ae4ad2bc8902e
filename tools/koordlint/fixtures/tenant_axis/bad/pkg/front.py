"""Seeded tenant-axis violations (must-flag corpus)."""

import jax
import jax.numpy as jnp


def _pass1(state, batch):
    return state


class Kit:
    def __init__(self):
        # koordlint: shape[arg0: NxR i32 nodes]
        self.pass1 = jax.jit(_pass1, donate_argnums=(0,))
        self._pass2_one = jax.jit(_pass1, donate_argnums=(0,))
        self._pass2_sh = jax.jit(_pass1, donate_argnums=(0,))
        self.sharded = False

    # koordlint: shape[state: NxR i32 nodes]
    def pass2(self, state, batch):
        # one entry per stage: the contract sits on the method
        if self.sharded:
            return self._pass2_sh(state, batch)
        return self._pass2_one(state, batch)


class Front:
    @staticmethod
    def _stack(trees):
        return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)

    @staticmethod
    def _unstack(tree, i):
        return jax.tree.map(lambda x: x[i], tree)

    def cycle(self, states, batches, tenants):
        stacked_state = self._stack(states)
        stacked_batch = self._stack(batches)
        a, st, est = self._batched(stacked_state, stacked_batch)
        for i, t in enumerate(tenants):
            # BAD: every adopted slice still carries the leading T axis
            t.scheduler.round_adopt_batched(a, st, est)
        return a

    def cycle_kit(self, states, batches, kit):
        stacked_state = self._stack(states)
        # BAD: the kit binding's shape annotation declares a per-tenant
        # arg0 but the call hands it the whole stacked tensor
        return kit.pass1(stacked_state, batches)

    def cycle_kit_entry(self, states, batches, kit):
        stacked_state = self._stack(states)
        # BAD: the same through a kit METHOD whose named parameter
        # carries the per-tenant annotation
        return kit.pass2(stacked_state, batches)

    # koordlint: shape[state: TxNxR i32]
    def adopt_annotated(self, state, tenants):
        # BAD: the T-leading annotated parameter is passed whole
        t = tenants[0]
        t.scheduler.round_adopt_batched(state)

    def _batched(self, state, batch):
        return state, batch, state
