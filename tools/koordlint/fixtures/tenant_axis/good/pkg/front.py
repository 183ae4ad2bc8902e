"""Tenant-axis-disciplined twins of the bad corpus (must-pass)."""

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
            # every slice explicitly reduced before the per-tenant sink
            t.scheduler.round_adopt_batched(
                self._unstack(a, i), self._unstack(st, i), est[i])
        return None

    def cycle_kit(self, states, batches, kit):
        for i, state in enumerate(states):
            # per-tenant dispatch feeds per-tenant shapes
            kit.pass1(state, batches[i])
            kit.pass2(state, batches[i])

    # koordlint: shape[state: TxNxR i32]
    def adopt_annotated(self, state, tenants):
        for i, t in enumerate(tenants):
            t.scheduler.round_adopt_batched(self._unstack(state, i))

    def unstack_inside_branch(self, states, handle, single):
        # the taint is discarded INSIDE the if body; the sink call that
        # follows must see the updated state, not the compound
        # statement's entry state
        a = self._stack(states)
        if single:
            a = self._unstack(a, 0)
            handle.scheduler.round_adopt_batched(handle, a)
        return a

    def _batched(self, state, batch):
        return state, batch, state
