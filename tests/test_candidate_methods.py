"""Forced candidate-selection methods (ops/batch_assign.select_candidates).

The TPU-serving branches — the approx_max_k float-key path and the
chunked reductions — are force-selectable via ``method=`` so CPU CI
executes them (no code path may run only where a TPU is attached).
Invariants asserted here:

- "approx": candidate recall vs the exact path >= 0.9 on seeded problems
  (on CPU the recall loss comes only from the 24-bit float-key
  quantization; on TPU approx_max_k adds its ~0.95 recall target), and the
  downstream acceptance stays EXACT — no node over capacity, no quota
  overshoot — because fit/quota checks never depend on the method;
- "chunked"/"chunked_exact": bit-exact with "approx"/"exact" respectively
  (chunking is an execution-schedule change only);
- "auto" resolves to "exact" on CPU; unknown methods raise.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from koordinator_tpu.ops.assignment import ScoringConfig
from koordinator_tpu.ops.batch_assign import (
    CANDIDATE_METHODS,
    batch_assign,
    select_candidates,
)
from tests.problem_helpers import build_problem as _build
from tests.problem_helpers import candidate_recall


def build_problem(n_nodes=256, n_pods=128, seed=0, factored=True):
    state, pods = _build(n_nodes=n_nodes, n_pods=n_pods, seed=seed,
                         classes=4, factored=factored)
    return state, pods, ScoringConfig.default()


def test_approx_method_recall_and_exact_acceptance():
    state, pods, cfg = build_problem(seed=1)
    ek, en = select_candidates(state, pods, cfg, k=16, method="exact")
    ak, an = select_candidates(state, pods, cfg, k=16, method="approx")
    rec = candidate_recall(np.asarray(en), np.asarray(ek), np.asarray(an))
    assert rec >= 0.9, f"approx candidate recall {rec:.3f} < 0.9"
    # gathered keys must be the exact int keys for the chosen nodes
    ek_map = {(p, int(n)): int(v)
              for p in range(en.shape[0])
              for n, v in zip(np.asarray(en)[p], np.asarray(ek)[p])}
    got = np.asarray(ak)
    for p in range(an.shape[0]):
        for n, v in zip(np.asarray(an)[p], got[p]):
            if (p, int(n)) in ek_map and v >= 0:
                assert v == ek_map[(p, int(n))]

    # acceptance is exact regardless of candidate method: replay the
    # assignment and check no node exceeds allocatable
    a, st, _ = batch_assign(state, pods, cfg, k=16, method="approx")
    a = np.asarray(a)
    req = np.asarray(pods.requests)
    used = np.asarray(state.node_requested).copy()
    for p in np.nonzero(a >= 0)[0]:
        used[a[p]] += req[p]
    assert (used <= np.asarray(state.node_allocatable)).all(), \
        "approx method let a node exceed capacity"
    np.testing.assert_array_equal(used, np.asarray(st.node_requested))


def test_auto_resolves_exact_on_cpu():
    state, pods, cfg = build_problem(n_nodes=64, n_pods=32, seed=4)
    ek, en = select_candidates(state, pods, cfg, k=8, method="exact")
    au_k, au_n = select_candidates(state, pods, cfg, k=8, method="auto")
    assert jax.default_backend() != "tpu"
    np.testing.assert_array_equal(np.asarray(ek), np.asarray(au_k))
    np.testing.assert_array_equal(np.asarray(en), np.asarray(au_n))


def test_unknown_method_raises():
    state, pods, cfg = build_problem(n_nodes=64, n_pods=32, seed=5)
    with pytest.raises(ValueError, match="unknown candidate method"):
        select_candidates(state, pods, cfg, method="fancy")
    assert "exact" in CANDIDATE_METHODS


class TestStratifiedCandidates:
    """spread_bits=(5, 15): score-faithful + coverage strata (the round-3
    fix for candidate exhaustion at the north-star shape)."""

    def test_split_math(self):
        from koordinator_tpu.ops.batch_assign import _stratum_splits

        assert _stratum_splits(32, 2) == [16, 16]
        assert _stratum_splits(15, 2) == [8, 7]
        assert _stratum_splits(8, 1) == [8]

    def test_stratified_exact_candidate_structure(self):
        state, pods, cfg = build_problem(n_nodes=128, n_pods=32, seed=7)
        ck, cn = select_candidates(
            state, pods, cfg, k=16, spread_bits=(5, 15), method="exact")
        assert cn.shape == (pods.capacity, 16)
        # first half = top-8 of the sb=5 key; second half = top-8 of the
        # pure-rotation key; ALL keys reported on the sb=5 scale
        k5, n5 = select_candidates(
            state, pods, cfg, k=8, spread_bits=5, method="exact")
        np.testing.assert_array_equal(np.asarray(cn)[:, :8],
                                      np.asarray(n5))
        np.testing.assert_array_equal(np.asarray(ck)[:, :8],
                                      np.asarray(k5))
        _, n15 = select_candidates(
            state, pods, cfg, k=8, spread_bits=15, method="exact")
        np.testing.assert_array_equal(np.asarray(cn)[:, 8:],
                                      np.asarray(n15))

    def test_coverage_stratum_rescues_exhausted_tail(self):
        # the north-star stranding phenomenon at CI scale (3,072 nodes x
        # 15k pods reproduces it in ~10s): diverse scores make the sb=5
        # tie groups narrow, the whole queue's candidate sets concentrate
        # on the top score band, and once it fills the tail's candidates
        # are all full even though the cluster has 3.6x headroom.  The
        # coverage stratum must assign the ENTIRE schedulable queue; the
        # single-key run must visibly strand (the test discriminates).
        from __graft_entry__ import _build_problem

        n_nodes, n_pods = 3_072, 15_000
        state, pods, cfg = _build_problem(n_nodes, n_pods, seed=42)
        a_strat, _, _ = jax.jit(
            lambda s: batch_assign(s, pods, cfg, k=16, method="approx"))(
            state)[:3]
        n_strat = int((np.asarray(a_strat) >= 0).sum())
        assert n_strat == n_pods, f"stratified stranded {n_pods - n_strat}"
        a_sb5, _, _ = jax.jit(
            lambda s: batch_assign(s, pods, cfg, k=16, spread_bits=5,
                                   method="approx"))(state)[:3]
        n_sb5 = int((np.asarray(a_sb5) >= 0).sum())
        assert n_sb5 < n_pods, "single-key run no longer strands; " \
            "update this scenario so the coverage property stays tested"


class TestChunkedCandidates:
    """method="chunked": the approx reduction over pod chunks via lax.map.
    Chunking is an execution-schedule change ONLY — scoring, global-offset
    rotation, and the per-row reduction are row-independent, so every row
    must be bit-identical to method="approx"."""

    @pytest.mark.parametrize("n_pods,chunk_note", [
        (100, "single partial chunk (P < chunk)"),
        (5000, "multiple chunks + padded tail"),
    ])
    def test_bit_identical_to_approx(self, n_pods, chunk_note):
        state, pods, cfg = build_problem(n_nodes=512, n_pods=n_pods, seed=3)
        run = jax.jit(select_candidates, static_argnames=("k", "method"))
        ck_a, cn_a = run(state, pods, cfg, k=16, method="approx")
        ck_c, cn_c = run(state, pods, cfg, k=16, method="chunked")
        assert np.array_equal(np.asarray(ck_a), np.asarray(ck_c)), chunk_note
        assert np.array_equal(np.asarray(cn_a), np.asarray(cn_c)), chunk_note

    def test_end_to_end_assignments_match(self):
        state, pods, cfg = build_problem(n_nodes=512, n_pods=5000, seed=4)
        run = jax.jit(batch_assign, static_argnames=("k", "rounds", "method"))
        a_approx, st_a, _ = run(state, pods, cfg, k=16, rounds=6,
                                method="approx")
        a_chunked, st_c, _ = run(state, pods, cfg, k=16, rounds=6,
                                 method="chunked")
        assert np.array_equal(np.asarray(a_approx), np.asarray(a_chunked))
        assert np.array_equal(np.asarray(st_a.node_requested),
                              np.asarray(st_c.node_requested))

    @pytest.mark.parametrize("n_pods,chunk_note", [
        (100, "single partial chunk (P < chunk)"),
        (5000, "multiple chunks + padded tail"),
    ])
    def test_chunked_exact_bit_identical_to_exact(self, n_pods, chunk_note):
        """method="chunked_exact": the TPU fallback when measured
        approx_max_k recall strands pods (bench_recall.py decision rule)
        — exact top_k rows at chunked peak memory.  Every row must be
        bit-identical to method="exact"."""
        state, pods, cfg = build_problem(n_nodes=512, n_pods=n_pods, seed=3)
        run = jax.jit(select_candidates, static_argnames=("k", "method"))
        ck_e, cn_e = run(state, pods, cfg, k=16, method="exact")
        ck_c, cn_c = run(state, pods, cfg, k=16, method="chunked_exact")
        assert np.array_equal(np.asarray(ck_e), np.asarray(ck_c)), chunk_note
        assert np.array_equal(np.asarray(cn_e), np.asarray(cn_c)), chunk_note

    def test_chunked_exact_end_to_end_assignments_match_exact(self):
        state, pods, cfg = build_problem(n_nodes=512, n_pods=5000, seed=4)
        run = jax.jit(batch_assign, static_argnames=("k", "rounds", "method"))
        a_e, st_e, _ = run(state, pods, cfg, k=16, rounds=6, method="exact")
        a_c, st_c, _ = run(state, pods, cfg, k=16, rounds=6,
                           method="chunked_exact")
        assert np.array_equal(np.asarray(a_e), np.asarray(a_c))
        assert np.array_equal(np.asarray(st_e.node_requested),
                              np.asarray(st_c.node_requested))

    def test_dense_feasible_batch_supported(self):
        # dense (P, N) masks chunk over the pod axis like everything else
        state, pods, cfg = build_problem(n_nodes=256, n_pods=300, seed=5,
                                         factored=False)
        run = jax.jit(select_candidates, static_argnames=("k", "method"))
        ck_a, cn_a = run(state, pods, cfg, k=8, method="approx")
        ck_c, cn_c = run(state, pods, cfg, k=8, method="chunked")
        assert np.array_equal(np.asarray(ck_a), np.asarray(ck_c))
        assert np.array_equal(np.asarray(cn_a), np.asarray(cn_c))


def test_gang_batch_solver_method_passthrough():
    """gang_assign(solver="batch", method=...) reaches the candidate
    stage: chunked and approx passes produce identical gang outcomes."""
    from koordinator_tpu.ops.gang import GangInfo, gang_assign

    state, pods, cfg = build_problem(n_nodes=256, n_pods=600, seed=6)
    gang_id = np.full(pods.capacity, -1, np.int32)
    gang_id[:32] = 0
    gpods = pods.replace(gang_id=jnp.asarray(gang_id))
    gangs = GangInfo.build(np.array([16], np.int32))
    run = jax.jit(gang_assign,
                  static_argnames=("passes", "solver", "method"))
    a_approx, _, _ = run(state, gpods, cfg, gangs, passes=2,
                         solver="batch", method="approx")
    a_chunked, _, _ = run(state, gpods, cfg, gangs, passes=2,
                          solver="batch", method="chunked")
    assert np.array_equal(np.asarray(a_approx), np.asarray(a_chunked))
    assert int((np.asarray(a_chunked) >= 0).sum()) > 0
