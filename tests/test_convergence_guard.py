"""Round-loop convergence across seeds at a mid shape, in DEFAULT CI.

The slow-marked north-star guards (test_north_star_shape.py) pin wave
convergence for one seed at the full 50k x 10,240 shape; the randomized
property suites sweep small shapes.  This is the cheap middle ground:
three seeds at 15k pods x 3,072 nodes under ~2x
capacity surplus must each converge to full placement within 3 waves —
keeping the contention-convergence claim honest without slow-CI cost.
One jit compile serves all seeds and waves (same shapes throughout).
"""

import jax
import jax.numpy as jnp
import numpy as np

from __graft_entry__ import _build_problem
from koordinator_tpu.ops.batch_assign import batch_assign

N_NODES = 3_072
N_PODS = 15_000
MAX_WAVES = 3


def test_moderate_load_converges_across_seeds():
    solve = None
    for seed in (1, 7, 42):
        state, pods, cfg = _build_problem(N_NODES, N_PODS, seed=seed)
        if solve is None:
            solve = jax.jit(lambda s, p, c: batch_assign(
                s, p, c, k=16, method="approx")[:2])
        # ~2x surplus: the same moderate-contention scaling the
        # north-star wave guard uses (11/20 of generated allocatable)
        st = state.replace(
            node_allocatable=(state.node_allocatable * 11) // 20)
        remaining = pods
        assigned = np.zeros(pods.capacity, bool)
        counts = []
        for _ in range(MAX_WAVES):
            asn, st = solve(st, remaining, cfg)
            wave = (np.asarray(asn) >= 0) & np.asarray(remaining.valid)
            counts.append(int(wave.sum()))
            assigned |= wave
            stranded = ~assigned & np.asarray(pods.valid)
            if not stranded.any():
                break
            remaining = remaining.replace(valid=jnp.asarray(stranded))
        assert (np.asarray(st.node_requested)
                <= np.asarray(st.node_allocatable)).all(), seed
        assert int(assigned.sum()) == N_PODS, (
            f"seed {seed}: waves {counts}, "
            f"{N_PODS - int(assigned.sum())} pods never placed")
        # wave 1 carries the bulk — the retry loop is a straggler
        # mechanism, not a crutch (same 95% bar as the north-star guard)
        assert counts[0] >= 0.95 * N_PODS, (seed, counts)
