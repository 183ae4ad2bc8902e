"""Solve quality AT THE NORTH-STAR SHAPE, in CI.

A single spread_bits=5 key held at a 2k-pod validation shape and
silently stranded 14% of pods at the real 50k x 10,240 shape; stratified
candidate selection fixed it (docs/solve_quality.md "Stratified candidates
at shape").  This test pins the real shape in CI (slow-marked:
`pytest -m slow`) so that class of regression can never ship silently
again.

The approx float-key candidate path is FORCED — the TPU-serving branch;
on CPU `approx_max_k`'s lowering is exact, so this isolates the
stratified-selection + float-key quantization behavior from TPU recall.
"""

import numpy as np
import pytest

from __graft_entry__ import _build_problem

pytestmark = pytest.mark.slow

NORTH_STAR_NODES = 10_240
NORTH_STAR_PODS = 50_000


@pytest.fixture(scope="module")
def problem():
    # seed 42 = the problem the single-key sb=5 regression was found on
    return _build_problem(NORTH_STAR_NODES, NORTH_STAR_PODS, seed=42)


@pytest.mark.parametrize("k", [8, 16, 32])
def test_stratified_candidates_assign_everything_at_shape(problem, k):
    import jax

    from koordinator_tpu.ops.batch_assign import batch_assign

    state, pods, cfg = problem
    valid = int(np.asarray(pods.valid).sum())
    assert valid == NORTH_STAR_PODS

    # pods traced, not closed over: closure capture would embed them as
    # HLO constants and constant-fold pod-dependent work at compile time
    asn, st = jax.jit(
        lambda s, p: batch_assign(s, p, cfg, k=k, method="approx")[:2]
    )(state, pods)
    asn = np.asarray(asn)

    assigned = int((asn >= 0).sum())
    # capacity must hold exactly...
    assert (np.asarray(st.node_requested)
            <= np.asarray(st.node_allocatable)).all()
    # ...and the stratified default must place every valid pod (the
    # round-2 bug left this at 0.86)
    assert assigned == valid, (
        f"k={k}: stranded {valid - assigned}/{valid} pods at the "
        f"north-star shape")


def _solve_waves(state, pods, cfg, max_waves: int):
    """Iterate batch_assign the way the scheduler's round loop does:
    unassigned pods retry against the updated state (fresh candidates).
    Returns (per-wave assigned counts, final state, assigned mask)."""
    import jax
    import jax.numpy as jnp

    from koordinator_tpu.ops.batch_assign import batch_assign

    solve = jax.jit(
        lambda s, p: batch_assign(s, p, cfg, k=16, method="approx")[:2])
    remaining, st = pods, state
    assigned_total = np.zeros(pods.capacity, bool)
    counts = []
    for _ in range(max_waves):
        asn, st = solve(st, remaining)
        wave = (np.asarray(asn) >= 0) & np.asarray(remaining.valid)
        counts.append(int(wave.sum()))
        assigned_total |= wave
        stranded = ~assigned_total & np.asarray(pods.valid)
        if not stranded.any() or counts[-1] == 0:
            break
        remaining = remaining.replace(valid=jnp.asarray(stranded))
    return counts, st, assigned_total


def test_moderate_load_converges_in_waves(problem):
    """At ~2x capacity surplus, a single solve strands ~3% of pods whose
    k=16 candidate windows all filled (candidates are chosen BEFORE the
    rounds).  The system-level behavior — the scheduler's round loop
    retries unassigned pods with fresh candidates — must converge to
    full placement within 3 waves (measured: 48,520 -> 1,470 -> 10 -> 0
    at this exact shape).  A candidate-coverage regression shows up as
    non-convergence."""
    state, pods, cfg = problem
    moderate = state.replace(
        node_allocatable=(state.node_allocatable * 11) // 20)
    counts, st, assigned = _solve_waves(moderate, pods, cfg, max_waves=3)
    assert (np.asarray(st.node_requested)
            <= np.asarray(st.node_allocatable)).all()
    assert int(assigned.sum()) == NORTH_STAR_PODS, (
        f"waves {counts}: {NORTH_STAR_PODS - int(assigned.sum())} pods "
        f"never placed despite available capacity")
    # the first wave alone must carry the overwhelming bulk — the retry
    # loop is a straggler mechanism, not a crutch.  95%: measured 97.0%
    # (48,520) at this seed; the margin absorbs tie-break perturbations
    # across jax/XLA versions without admitting a real coverage
    # regression (the round-2 bug was at 86%)
    assert counts[0] >= 0.95 * NORTH_STAR_PODS, counts


def test_contended_queue_respects_capacity_and_priority(problem):
    """TRUE contention (capacity < demand, ~15% of the original
    allocatable): after the retry waves settle, (a) capacity holds
    exactly, (b) no stranded pod has a feasible node left by the
    solver's own fit rule (no missed opportunity at the fixed point),
    and (c) assigned pods skew clearly above stranded ones in priority
    (the in-round rule is priority wins conflicts, not a strict global
    cut, so the assertion is distributional)."""
    import jax

    from koordinator_tpu.ops.assignment import score_pods

    state, pods, cfg = problem
    contended = state.replace(
        node_allocatable=(state.node_allocatable * 3) // 20)
    counts, st, assigned = _solve_waves(contended, pods, cfg, max_waves=4)
    alloc = np.asarray(st.node_allocatable)
    used = np.asarray(st.node_requested)
    valid = np.asarray(pods.valid)

    # (a) capacity holds exactly on every dim of every node
    assert (used <= alloc).all()
    n_assigned = int(assigned.sum())
    assert 0 < n_assigned < NORTH_STAR_PODS, counts   # genuinely short

    # (b) no missed opportunity once the waves settle
    has_feasible = np.asarray(jax.jit(
        lambda s, p: score_pods(s, p, cfg)[1].any(axis=1))(st, pods))
    missed = ~assigned & valid & has_feasible
    assert int(missed.sum()) == 0, (
        f"{int(missed.sum())} stranded pods still had a feasible node "
        f"after waves {counts}")

    # (c) priority skew: assigned pods outrank stranded ones clearly
    prio = np.asarray(pods.priority)
    mean_assigned = prio[assigned & valid].mean()
    mean_stranded = prio[~assigned & valid].mean()
    assert mean_assigned - mean_stranded > 500, (
        f"assigned {mean_assigned:.0f} vs stranded {mean_stranded:.0f}")


def test_double_shape_headroom():
    """2x the north star (100k pods x 20,480 nodes) on the chunked
    path: full assignment, exact capacity — the shape ceiling is not
    near the target (measured 97s wall on CPU, compile-dominated)."""
    import jax

    from koordinator_tpu.ops.batch_assign import batch_assign

    state, pods, cfg = _build_problem(20_480, 100_000, seed=7)
    asn, st = jax.jit(
        lambda s, p: batch_assign(s, p, cfg, k=16, method="chunked")[:2]
    )(state, pods)
    asn = np.asarray(asn)
    valid = int(np.asarray(pods.valid).sum())
    assert int((asn >= 0).sum()) == valid
    assert (np.asarray(st.node_requested)
            <= np.asarray(st.node_allocatable)).all()


def test_chunked_exact_assigns_everything_at_shape(problem):
    """The recall-exact TPU fallback (method="chunked_exact" — exact
    top_k rows at chunked peak memory) must hold the same
    100%-assignment bar as the default at the real shape: it is what
    method="auto"'s TPU arm flips to if bench_recall.py measures
    approx_max_k stranding pods."""
    import jax

    from koordinator_tpu.ops.batch_assign import batch_assign

    state, pods, cfg = problem
    valid = int(np.asarray(pods.valid).sum())
    asn, st = jax.jit(
        lambda s, p: batch_assign(s, p, cfg, k=16,
                                  method="chunked_exact")[:2]
    )(state, pods)
    asn = np.asarray(asn)
    assert (np.asarray(st.node_requested)
            <= np.asarray(st.node_allocatable)).all()
    assert int((asn >= 0).sum()) == valid
