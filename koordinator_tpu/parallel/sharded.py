"""2-D (pods x nodes) ``shard_map`` solve: the sharded-by-default path.

The batched solver's stages — fused Filter+Score candidate selection,
the propose/accept rounds, the incremental dirty refresh, the gang
all-or-nothing passes and the exact greedy scan — run here as explicit
SPMD programs over the full 2-D ``solver_mesh``:

- **node tensors** (``ClusterState``, ``est_accum``) shard their leading
  axis over ``NODES_AXIS`` and replicate over ``PODS_AXIS``; shard ``s``
  owns global rows ``[s*N/dn, (s+1)*N/dn)``.
- **pod tensors** (``PodBatch``, the (P, k) candidate cache) shard their
  leading axis over ``PODS_AXIS`` and replicate over ``NODES_AXIS``.
  With ``pods_axis == 1`` (the default mesh) this is exactly the PR-10
  replicated layout, bit for bit and program for program.
- the (P, N) score/rank work — the dominant footprint at the 50k-pod
  north-star shape — therefore lands as (P/dp, N/dn) tiles: per-device
  candidate/score bytes scale 1/pods_axis at fixed total devices.

Exactness argument — sharded acceptance decisions are BIT-IDENTICAL to
the single-device solve at every mesh shape:

- **Selection** is per-(pod-shard, node-shard)-tile local top-k with a
  two-stage cross-axis merge.  Stage 1 (within a pod-shard row): each
  tile reduces its local columns to the per-pod per-stratum
  top-``min(k_i, n_local)`` by the GLOBAL ranking key
  (``ops/batch_assign._rank_parts`` with global node ids), the
  (P_loc, m) tile winners ride one ``all_gather`` over ``NODES_AXIS``,
  and every tile re-ranks the gathered union with the same
  ``_topk_by_rank``.  The top-k of a union of per-shard top-k's equals
  the top-k of all columns (an element outside its shard's top-k is
  dominated by k_i better local elements), and rank pairs are unique
  per pod, so each pod row's merged sequence — values AND order —
  equals the single-device output exactly.  Stage 2 (across the pod
  axis): pod rows are INDEPENDENT, so the pod-sharded (P_loc, k)
  results simply reassemble as the (P, k) global array — no cross-pod
  merge exists to be wrong.
- **Rounds**: the (P, k) candidates and per-pod tensors are gathered
  over ``PODS_AXIS`` ONCE, before the round loop (gathering per round
  is the regression koordlint's pod-axis corpus pins); every per-round
  decision (best fitting candidate, priority-prefix acceptance, quota
  admission) is then computed REPLICATED over the pod axis from the
  gathered inputs, exactly as PR 10 computed it replicated over the
  node axis.  The only node-sharded data — per-candidate free capacity
  — is owned along ``NODES_AXIS`` and combined with an int32 ``psum``
  (exact: exactly one shard contributes a nonzero term per candidate).
  The replicated acceptance equals ``ops/batch_assign._assign_rounds``
  term for term; each node shard scatters accepted requests only into
  rows it owns.
- **Refresh**: a dirty node rescores only on the owning
  (pod-shard, node-shard) TILE — pods enter as local rows, unowned
  dirty nodes enter the (P_loc, D) sub-problem as invalid — the
  per-tile dirty winners are all-gathered over ``NODES_AXIS``, and the
  merge re-ranks cached ∪ fresh per pod row on one key scale: the same
  union-of-top-k argument as selection, pod rows independent.
- **Gang / greedy**: the gang pass loop (select + rounds + rollback +
  est accumulation) runs the kernels above per pass with the rollback
  decisions replicated from gathered (P,) flags and the rebuilt
  ``node_requested`` owner-scattered; the greedy scan keeps its
  sequential pod order with each step's argmax merged over
  ``NODES_AXIS`` as (max score, then min global node id among the
  ties) — exactly ``jnp.argmax``'s first-occurrence rule — so neither
  path all-gathers the (P, N) problem the way GSPMD placement did.

Candidate selection here is always recall-EXACT (the per-tile problem
is a factor of ``dp*dn`` smaller, so exact ``top_k`` is affordable
where the single-device path reaches for ``approx_max_k``).

Capacity: the node capacity must divide by the mesh's nodes axis and
the pod-batch capacity by the pods axis — power-of-two capacity
bucketing (state/cluster_state, ``PodBatch.build``/``compact``)
guarantees both for power-of-two axis sizes.  The packed-vs-wide
ranking-key regime (``ops/batch_assign``) is orthogonal: keys are
global in both regimes, which is why sharding composes with the
>32,768-node wide regime.
"""

from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from koordinator_tpu.ops import batch_assign as ba
from koordinator_tpu.ops.assignment import pod_estimates, score_pods
from koordinator_tpu.parallel.mesh import (
    NODES_AXIS,
    PODS_AXIS,
    nodes_shard_count,
    pods_shard_count,
)
from koordinator_tpu.quota.admission import (
    charge_quota,
    charge_quota_batch,
    quota_admission_mask,
)

_NODES = P(NODES_AXIS)   # leading (node) axis sharded, pods-replicated
_PODS = P(PODS_AXIS)     # leading (pod) axis sharded, nodes-replicated
_REP = P()               # replicated over the whole mesh


def check_shardable(n_total: int, mesh) -> None:
    """Loud trace-time guard: the node capacity must split evenly over
    the mesh's nodes axis."""
    d = nodes_shard_count(mesh)
    if n_total % d:
        raise ValueError(
            f"node capacity {n_total} does not divide over the mesh's "
            f"{d}-way nodes axis; power-of-two capacity bucketing "
            "(state/cluster_state._bucket) guarantees divisibility for "
            "power-of-two device counts")


def check_pod_shardable(p_total: int, mesh) -> None:
    """Loud trace-time guard: the pod-batch capacity must split evenly
    over the mesh's pods axis."""
    d = pods_shard_count(mesh)
    if p_total % d:
        raise ValueError(
            f"pod-batch capacity {p_total} does not divide over the "
            f"mesh's {d}-way pods axis; PodBatch's power-of-two "
            "bucketing (build/compact) guarantees divisibility for "
            "power-of-two pods_axis sizes")


def _shard_offset(n_local: int) -> jnp.ndarray:
    """Global node row of this tile's local node row 0."""
    return jax.lax.axis_index(NODES_AXIS).astype(jnp.int32) * n_local


def _pod_offset(p_local: int) -> jnp.ndarray:
    """Global pod row of this tile's local pod row 0."""
    return jax.lax.axis_index(PODS_AXIS).astype(jnp.int32) * p_local


def _gather_pods(tree):
    """All-gather a pod-sharded pytree over the pods axis — ONCE, before
    any round loop (a per-round pod-axis gather is the regression the
    koordlint spec-consistency corpus pins).  Identity on a 1-way pods
    axis, so the default mesh compiles the PR-10 program unchanged."""
    return jax.tree.map(
        lambda x: jax.lax.all_gather(x, PODS_AXIS, axis=0, tiled=True),
        tree)


# ---------------------------------------------------------------------------
# Selection: per-tile local top-k + cross-axis segmented merge
# ---------------------------------------------------------------------------


# koordlint: shape[st_local: NxR i32 nodes]
def _local_select_body(st_local, pods, cfg, *, k, strata, n_total):
    """Tile-local fused Filter+Score + per-stratum local top-k, then the
    cross-node-shard merge.  ``pods`` holds this tile's LOCAL pod rows;
    returns the pod-sharded (cand_key, cand_node, cand_score) — the
    ``with_scores=True`` shape of ``ops/batch_assign.select_candidates``
    for those rows."""
    n_loc = st_local.capacity
    off = _shard_offset(n_loc)
    with jax.named_scope("score"):
        scores, feasible = score_pods(st_local, pods, cfg)  # (P_loc, n_loc)
    node_ids = off + jnp.arange(n_loc, dtype=jnp.int32)
    clipped = jnp.clip(scores, 0, ba._SCORE_CLIP)
    rot = pods.rot_id

    with jax.named_scope("select"):
        splits = ba._stratum_splits(k, len(strata))
        nodes_out, scores_out = [], []
        for sb, k_i in zip(strata, splits):
            if k_i == 0:
                continue
            key, tb = ba._rank_parts(scores, feasible, sb, rot,
                                     node_ids=node_ids, n_total=n_total)
            m_i = min(k_i, n_loc)
            val, idx = ba._topk_by_rank(key, tb, m_i, n_total)
            sel_node = node_ids[idx]
            sel_score = jnp.where(
                val >= 0, jnp.take_along_axis(clipped, idx, axis=1), -1)
            # cross-shard segmented top-k merge: (P_loc, m) tile winners
            # ride one all_gather over the nodes axis, every tile re-ranks
            # the union globally; pod rows are independent, so no pod-axis
            # merge exists
            g_node = jax.lax.all_gather(sel_node, NODES_AXIS, axis=1,
                                        tiled=True)
            g_score = jax.lax.all_gather(sel_score, NODES_AXIS, axis=1,
                                         tiled=True)
            g_key = ba._candidate_keys(g_score, g_node, rot, sb, n_total)
            mval, midx = ba._topk_by_rank(
                g_key, ba._candidate_tb(g_node, rot, n_total), k_i, n_total)
            nodes_out.append(jnp.take_along_axis(g_node, midx, axis=1))
            scores_out.append(jnp.where(
                mval >= 0, jnp.take_along_axis(g_score, midx, axis=1), -1))

        cand_node = (jnp.concatenate(nodes_out, axis=1)
                     if len(nodes_out) > 1 else nodes_out[0])
        cand_score = (jnp.concatenate(scores_out, axis=1)
                      if len(scores_out) > 1 else scores_out[0])
        cand_key = ba._candidate_keys(cand_score, cand_node, rot,
                                      strata[0], n_total)
    return cand_key, cand_node, cand_score


@lru_cache(maxsize=None)
def _select_program(mesh, n_total, k, strata):
    """Jitted shard_map selection program, memoized on its statics.

    Every sharded entry point memoizes its jitted program this way:
    shard_map traced eagerly re-dispatches op by op on EVERY call (and
    re-traces per fresh ``partial`` closure), which made repeated
    direct calls — the mesh-invariance sweeps, the dirty-node refresh
    loops, bench stages — pay trace + per-op dispatch each time.
    ``Mesh`` hashes by (devices, axis names), so equal meshes share the
    entry (2-D shapes hash by their device GRID, so 2x4 and 1x8 are
    distinct entries), and the kit's outer jit composes (nested jit
    inlines)."""
    return jax.jit(jax.shard_map(
        partial(_local_select_body, k=k, strata=strata, n_total=n_total),
        mesh=mesh, in_specs=(_NODES, _PODS, _REP),
        out_specs=(_PODS, _PODS, _PODS), check_vma=False))


def sharded_select_candidates(mesh, state, pods, cfg, k: int = ba.CAND_K,
                              spread_bits=ba.CAND_SPREAD_BITS,
                              with_scores: bool = False):
    """``select_candidates`` over the 2-D mesh (recall-exact).

    Bit-identical to the single-device ``method="exact"`` selection on
    valid slots (see module docstring); the returned (P, k) tensors are
    pod-axis-sharded."""
    strata = (tuple(spread_bits) if isinstance(spread_bits, (tuple, list))
              else (spread_bits,))
    n_total = state.capacity
    check_shardable(n_total, mesh)
    check_pod_shardable(pods.capacity, mesh)
    k = min(k, n_total)
    fn = _select_program(mesh, n_total, k, strata)
    cand_key, cand_node, cand_score = fn(state, pods, cfg)
    if with_scores:
        return cand_key, cand_node, cand_score
    return cand_key, cand_node


# ---------------------------------------------------------------------------
# Rounds: pod-axis gather ONCE, replicated acceptance, owner-psum capacity
# ---------------------------------------------------------------------------


@jax.named_scope("assign_rounds")
def _rounds_local(st_local, pods, quota, cand_key, cand_node, *,
                  rounds, n_total):
    """The propose/accept loop over GATHERED (full-P) pod tensors with
    node tensors shard-local.  Mirrors
    ``ops/batch_assign._assign_rounds`` decision for decision; returns
    (assignments, requested_local, quota)."""
    n_loc = st_local.capacity
    off = _shard_offset(n_loc)
    cand_valid = cand_key >= 0
    cand_tb = (None if ba._packed_regime(n_total)
               else ba._candidate_tb(cand_node, pods.rot_id, n_total))
    order = jnp.lexsort((jnp.arange(pods.capacity), -pods.priority))
    active0 = pods.valid & jnp.any(cand_valid, axis=1)

    local = cand_node - off
    own = (local >= 0) & (local < n_loc)           # (P, k) owner mask
    local_c = jnp.clip(local, 0, n_loc - 1)

    def round_body(c):
        requested, assignments, active, qstate = c
        with jax.named_scope("propose"):
            free_loc = jnp.where(
                st_local.node_valid[:, None],
                st_local.node_allocatable - requested, 0)
            # per-candidate free capacity: the owning shard contributes,
            # the int32 psum reassembles the exact global gather
            # free[cand_node]
            cand_free = jax.lax.psum(
                jnp.where(own[:, :, None], free_loc[local_c], 0),
                NODES_AXIS)
            fits = jnp.all(
                (pods.requests[:, None, :] <= cand_free)
                | (pods.requests[:, None, :] == 0),
                axis=-1,
            ) & cand_valid
            best = ba._choose_candidate(cand_key, cand_tb, fits)
            has = jnp.take_along_axis(fits, best[:, None], axis=1)[:, 0]
            choice = jnp.take_along_axis(
                cand_node, best[:, None], axis=1)[:, 0]

        act = active & has
        if qstate is not None:
            act = act & quota_admission_mask(
                qstate, pods.requests, pods.quota_id, pods.non_preemptible)

        loc_choice = choice - off
        own_c = (loc_choice >= 0) & (loc_choice < n_loc)
        loc_choice_c = jnp.clip(loc_choice, 0, n_loc - 1)
        with jax.named_scope("prefix_accept"):
            choice_free = jax.lax.psum(
                jnp.where((own_c & act)[:, None],
                          free_loc[loc_choice_c], 0),
                NODES_AXIS)
            accept = ba._prefix_accept_choice(
                choice, pods.requests, choice_free, n_total, order, act)
        if qstate is not None:
            with jax.named_scope("quota_accept"):
                accept = accept & ba._quota_prefix_accept(
                    qstate, pods.requests, pods, order, act)

        add = jnp.where((accept & own_c)[:, None], pods.requests, 0)
        requested = requested.at[loc_choice_c].add(add)
        new_quota = qstate
        if new_quota is not None:
            new_quota = charge_quota_batch(
                new_quota, pods.requests, pods.quota_id, accept,
                pods.non_preemptible)
        return (requested,
                jnp.where(accept, choice, assignments),
                act & ~accept,
                new_quota)

    def cond(loop_carry):
        i, c = loop_carry
        return (i < rounds) & jnp.any(c[2])

    def body(loop_carry):
        i, c = loop_carry
        return i + 1, round_body(c)

    carry = (st_local.node_requested,
             jnp.full(pods.capacity, -1, jnp.int32),
             active0, quota)
    _, carry = jax.lax.while_loop(cond, body, (jnp.int32(0), carry))
    return carry[1], carry[0], carry[3]


# koordlint: shape[st_local: NxR i32 nodes, cand_key: Pxk i32 pods, cand_node: Pxk i32 pods]
def _rounds_body(st_local, pods, quota, cand_key, cand_node, *,
                 rounds, n_total):
    # ONE pod-axis gather, before the round loop: the acceptance oracle
    # (priority prefix over ALL pods) is global by definition
    pods, cand_key, cand_node = _gather_pods((pods, cand_key, cand_node))
    a, requested, new_quota = _rounds_local(
        st_local, pods, quota, cand_key, cand_node,
        rounds=rounds, n_total=n_total)
    return a, st_local.replace(node_requested=requested), new_quota


@lru_cache(maxsize=None)
def _rounds_program(mesh, n_total, rounds):
    """Jitted shard_map rounds program (see :func:`_select_program`)."""
    return jax.jit(jax.shard_map(
        partial(_rounds_body, rounds=rounds, n_total=n_total),
        mesh=mesh, in_specs=(_NODES, _PODS, _REP, _PODS, _PODS),
        out_specs=(_REP, _NODES, _REP), check_vma=False))


def sharded_assign_rounds(mesh, state, pods, quota, cand_key, cand_node,
                          rounds: int = ba.SOLVE_ROUNDS):
    """``_assign_rounds`` over the mesh: (assignments, new_state, quota)."""
    n_total = state.capacity
    check_shardable(n_total, mesh)
    check_pod_shardable(pods.capacity, mesh)
    return _rounds_program(mesh, n_total, rounds)(
        state, pods, quota, cand_key, cand_node)


# koordlint: shape[st_local: NxR i32 nodes, cand_key: Pxk i32 pods, cand_node: Pxk i32 pods]
def _round_pass_body(st_local, pods, quota, cand_key, cand_node, cfg, *,
                     rounds, n_total):
    pods, cand_key, cand_node = _gather_pods((pods, cand_key, cand_node))
    a, requested, _ = _rounds_local(
        st_local, pods, quota, cand_key, cand_node,
        rounds=rounds, n_total=n_total)
    n_loc = st_local.capacity
    off = _shard_offset(n_loc)
    keep = a >= 0
    est = pod_estimates(pods, cfg)
    loc = a - off
    own = keep & (loc >= 0) & (loc < n_loc)
    est_accum = jnp.zeros_like(st_local.node_usage).at[
        jnp.clip(loc, 0, n_loc - 1)
    ].add(jnp.where(own[:, None], est, 0))
    new_quota = quota
    if quota is not None:
        # in-rounds quota feedback is discarded and recharged whole,
        # exactly as the single-device assign_round_pass does
        new_quota = charge_quota_batch(
            quota, pods.requests, pods.quota_id, keep,
            pods.non_preemptible)
    return (a, st_local.replace(node_requested=requested), new_quota,
            est_accum)


@lru_cache(maxsize=None)
def _round_pass_program(mesh, n_total, rounds):
    """Jitted shard_map pass-1 program (see :func:`_select_program`)."""
    return jax.jit(jax.shard_map(
        partial(_round_pass_body, rounds=rounds, n_total=n_total),
        mesh=mesh, in_specs=(_NODES, _PODS, _REP, _PODS, _PODS, _REP),
        out_specs=(_REP, _NODES, _REP, _NODES), check_vma=False))


def sharded_assign_round_pass(mesh, state, pods, quota, cand_key,
                              cand_node, cfg,
                              rounds: int = ba.SOLVE_ROUNDS):
    """``assign_round_pass`` over the mesh: first solve pass over
    precomputed candidates with est-usage accumulation and whole-batch
    quota recharge.  Returns (assignments, new_state, new_quota,
    est_accum); ``est_accum`` is node-sharded like the state."""
    n_total = state.capacity
    check_shardable(n_total, mesh)
    check_pod_shardable(pods.capacity, mesh)
    return _round_pass_program(mesh, n_total, rounds)(
        state, pods, quota, cand_key, cand_node, cfg)


def _followup_body(st_local, est_local, pods, quota, cfg, *,
                   k, strata, rounds, n_total):
    # candidates re-selected against the est-augmented state; rounds and
    # the commit run against the UN-augmented accounting (the
    # assign_followup_pass rollback-rebuild semantics).  Selection runs
    # on this tile's LOCAL pod rows; the (P_loc, k) winners then ride
    # the one pod-axis gather into the replicated rounds.
    aug = st_local.replace(
        node_usage=st_local.node_usage + est_local,
        node_agg_usage=st_local.node_agg_usage + est_local)
    ck_loc, cn_loc, _ = _local_select_body(
        aug, pods, cfg, k=k, strata=strata, n_total=n_total)
    pods, cand_key, cand_node = _gather_pods((pods, ck_loc, cn_loc))
    a, requested, _ = _rounds_local(
        aug, pods, quota, cand_key, cand_node,
        rounds=rounds, n_total=n_total)
    n_loc = st_local.capacity
    off = _shard_offset(n_loc)
    keep = (a >= 0) & pods.valid
    est = pod_estimates(pods, cfg)
    loc = a - off
    own = keep & (loc >= 0) & (loc < n_loc)
    loc_c = jnp.clip(loc, 0, n_loc - 1)
    est_accum = est_local.at[loc_c].add(jnp.where(own[:, None], est, 0))
    new_quota = quota
    if quota is not None:
        new_quota = charge_quota_batch(
            quota, pods.requests, pods.quota_id, keep,
            pods.non_preemptible)
    # aug and st_local share node_requested, so the rounds' requested IS
    # the committed accounting (original + accepted requests)
    return (a, st_local.replace(node_requested=requested), new_quota,
            est_accum)


@lru_cache(maxsize=None)
def _followup_program(mesh, n_total, k, strata, rounds):
    """Jitted shard_map follow-up program (see :func:`_select_program`)."""
    return jax.jit(jax.shard_map(
        partial(_followup_body, k=k, strata=strata,
                rounds=rounds, n_total=n_total),
        mesh=mesh, in_specs=(_NODES, _NODES, _PODS, _REP, _REP),
        out_specs=(_REP, _NODES, _REP, _NODES), check_vma=False))


def sharded_assign_followup_pass(mesh, state, est_accum, pods, quota, cfg,
                                 k: int = ba.CAND_K,
                                 rounds: int = ba.SOLVE_ROUNDS,
                                 spread_bits=ba.CAND_SPREAD_BITS):
    """``assign_followup_pass`` over the mesh (selection is always
    recall-exact here).  Returns (assignments, new_state, new_quota,
    est_accum')."""
    strata = (tuple(spread_bits) if isinstance(spread_bits, (tuple, list))
              else (spread_bits,))
    n_total = state.capacity
    check_shardable(n_total, mesh)
    check_pod_shardable(pods.capacity, mesh)
    return _followup_program(mesh, n_total, min(k, n_total), strata,
                             rounds)(state, est_accum, pods, quota, cfg)


# ---------------------------------------------------------------------------
# Incremental refresh: owning-tile dirty rescore + nodes-axis merge
# ---------------------------------------------------------------------------


# koordlint: shape[st_local: NxR i32 nodes]
@jax.named_scope("refresh")
def _refresh_body(st_local, pods, cfg, cache, dirty_rows, dirty_valid, *,
                  k, strata, n_total):
    n_loc = st_local.capacity
    off = _shard_offset(n_loc)
    rot = pods.rot_id
    d = dirty_rows.shape[0]

    # a dirty node rescores only on its owning TILE: pods enter as this
    # tile's local rows, unowned dirty nodes enter the (P_loc, D)
    # sub-problem as invalid and rank -1
    loc = dirty_rows - off
    own = (loc >= 0) & (loc < n_loc) & dirty_valid
    sub = st_local.gather_rows(jnp.clip(loc, 0, n_loc - 1), own)
    with jax.named_scope("score"):
        scores, feasible = score_pods(sub, pods, cfg)       # (P_loc, D)
    clipped = jnp.clip(scores, 0, ba._SCORE_CLIP)

    # global dirty mask (nodes-replicated): cached slots pointing at ANY
    # dirty node are stale regardless of which shard owns it
    dirty_mask = jnp.zeros(n_total, bool).at[dirty_rows].max(dirty_valid)
    stale_score = jnp.where(dirty_mask[cache.cand_node], -1,
                            cache.cand_score)

    splits = ba._stratum_splits(k, len(strata))
    nodes_out, scores_out = [], []
    offset = 0
    for sb, k_i in zip(strata, splits):
        if k_i == 0:
            continue
        seg_node = cache.cand_node[:, offset:offset + k_i]
        seg_score = stale_score[:, offset:offset + k_i]
        offset += k_i
        dkey, dtb = ba._rank_parts(scores, feasible, sb, rot,
                                   node_ids=dirty_rows, n_total=n_total)
        m_i = min(k_i, d)
        dval, idx = ba._topk_by_rank(dkey, dtb, m_i, n_total)
        d_node = dirty_rows[idx]
        d_score = jnp.where(
            dval >= 0, jnp.take_along_axis(clipped, idx, axis=1), -1)
        g_node = jax.lax.all_gather(d_node, NODES_AXIS, axis=1, tiled=True)
        g_score = jax.lax.all_gather(d_score, NODES_AXIS, axis=1,
                                     tiled=True)
        # merge re-ranks per pod row: cached ∪ per-shard fresh winners
        # on one key scale (pod rows independent — no pod-axis merge)
        c_key = ba._candidate_keys(seg_score, seg_node, rot, sb, n_total)
        g_key = ba._candidate_keys(g_score, g_node, rot, sb, n_total)
        m_key = jnp.concatenate([c_key, g_key], axis=1)
        m_node = jnp.concatenate([seg_node, g_node], axis=1)
        m_score = jnp.concatenate([seg_score, g_score], axis=1)
        mval, midx = ba._topk_by_rank(
            m_key, ba._candidate_tb(m_node, rot, n_total), k_i, n_total)
        nodes_out.append(jnp.take_along_axis(m_node, midx, axis=1))
        scores_out.append(jnp.where(
            mval >= 0, jnp.take_along_axis(m_score, midx, axis=1), -1))

    cand_node = (jnp.concatenate(nodes_out, axis=1)
                 if len(nodes_out) > 1 else nodes_out[0])
    cand_score = (jnp.concatenate(scores_out, axis=1)
                  if len(scores_out) > 1 else scores_out[0])
    cand_key = ba._candidate_keys(cand_score, cand_node, rot,
                                  strata[0], n_total)
    return cand_key, ba.CandidateCache(cand_key, cand_node, cand_score)


@lru_cache(maxsize=None)
def _refresh_program(mesh, n_total, k, strata):
    """Jitted shard_map refresh program (see :func:`_select_program`)."""
    return jax.jit(jax.shard_map(
        partial(_refresh_body, k=k, strata=strata, n_total=n_total),
        mesh=mesh, in_specs=(_NODES, _PODS, _REP, _PODS, _REP, _REP),
        out_specs=(_PODS, _PODS), check_vma=False))


def sharded_refresh_candidates(mesh, state, pods, cfg, cache, dirty_rows,
                               dirty_valid, k: int = ba.CAND_K,
                               spread_bits=ba.CAND_SPREAD_BITS):
    """``refresh_candidates`` over the mesh: dirty columns rescore on
    their owning (pod, node) tile, the merge re-ranks per pod row.
    Returns (cand_key, new_cache) like the single-device refresh, both
    pod-axis-sharded."""
    strata = (tuple(spread_bits) if isinstance(spread_bits, (tuple, list))
              else (spread_bits,))
    n_total = state.capacity
    check_shardable(n_total, mesh)
    check_pod_shardable(pods.capacity, mesh)
    return _refresh_program(mesh, n_total, min(k, n_total), strata)(
        state, pods, cfg, cache, dirty_rows, dirty_valid)


# ---------------------------------------------------------------------------
# Gang all-or-nothing + exact greedy: the explicit shard_map twins of the
# GSPMD-placed ops/gang.gang_assign and ops/assignment.greedy_assign paths
# ---------------------------------------------------------------------------


def _greedy_local(st_local, pods, cfg, quota):
    """Shard-local exact greedy scan over GATHERED (full-P) pods:
    mirrors ``ops/assignment._greedy_scan`` (no reservations) step for
    step — the same Filter (``scan_filter``), the same entry filter
    (``scan_alive`` on the local node shard, merged with ONE ``pmax`` over
    the nodes axis before the loop), the same visiting order and trip
    count — with the per-step argmax merged over the nodes axis as
    (max score, then MIN global node id among the ties) — equal to the
    single-device ``jnp.argmax`` first-occurrence rule, because the
    local argmax already picks the lowest local index and global ids
    order identically to local ones within a shard.

    Returns (assignments, requested, new_quota, steps)."""
    from koordinator_tpu.ops.assignment import (
        _composite_score,
        live_first,
        scan_alive,
        scan_filter,
    )

    n_loc = st_local.capacity
    off = _shard_offset(n_loc)
    node_ids = off + jnp.arange(n_loc, dtype=jnp.int32)
    pod_est_all = pod_estimates(pods, cfg)
    alive = jax.lax.pmax(
        scan_alive(st_local, pods, pod_est_all, cfg, quota).astype(jnp.int32),
        NODES_AXIS) > 0
    order = live_first(pods, alive)
    n_live = jnp.sum(alive, dtype=jnp.int32)

    def step(i, carry):
        requested, est_added, qstate, nodes = carry
        idx = order[i]
        row = jax.tree.map(lambda a: a[idx][None], pods)
        req = pods.requests[idx]
        pod_est = pod_est_all[idx]
        feasible = scan_filter(
            st_local, row, pod_est[None, :], cfg, requested, est_added,
            qstate, None)[0]
        scores = _composite_score(
            cfg, st_local.node_allocatable, requested,
            st_local.node_usage + est_added,
            req[None, :], pod_est[None, :])[0]
        masked = jnp.where(feasible, scores, -1)
        lbest = jnp.argmax(masked)
        lscore = masked[lbest]
        gscore = jax.lax.pmax(lscore, NODES_AXIS)
        cand = jnp.where(lscore == gscore, node_ids[lbest],
                         jnp.int32(2**30))
        gnode = jax.lax.pmin(cand, NODES_AXIS)
        assigned = gscore >= 0
        node = jnp.where(assigned, gnode, -1)
        loc = gnode - off
        own = assigned & (loc >= 0) & (loc < n_loc)
        loc_c = jnp.clip(loc, 0, n_loc - 1)
        requested = requested.at[loc_c].add(jnp.where(own, req, 0))
        est_added = est_added.at[loc_c].add(jnp.where(own, pod_est, 0))
        if qstate is not None:
            qstate = charge_quota(
                qstate, jnp.where(assigned, req, 0),
                jnp.where(assigned, pods.quota_id[idx], -1),
                non_preemptible=pods.non_preemptible[idx])
        return requested, est_added, qstate, nodes.at[idx].set(node)

    requested, _, new_quota, assignments = jax.lax.fori_loop(
        0, n_live, step,
        (st_local.node_requested, jnp.zeros_like(st_local.node_usage), quota,
         jnp.full(pods.capacity, -1, jnp.int32)))
    return assignments, requested, new_quota, n_live


# koordlint: shape[st_local: NxR i32 nodes]
def _gang_body(st_local, pods, cfg, gangs, quota, *, passes, solver,
               k, strata, rounds, n_total, p_total):
    """The gang all-or-nothing pass loop as one SPMD program: per pass,
    solve (batch select+rounds or the greedy scan), count per-gang
    placements from replicated flags, roll failed groups back by
    REBUILDING the owner-local ``node_requested`` from the pre-pass
    accounting plus only the kept pods (ops/gang.rollback_failed_gangs'
    exact-rollback rule), accumulate kept pods' estimated usage into the
    owner shard, and recharge quota whole.  Mirrors
    ``ops/gang.gang_assign`` decision for decision."""
    from koordinator_tpu.ops.gang import (
        _group_ok,
        _per_gang_counts,
        pre_enqueue_mask,
    )

    n_loc = st_local.capacity
    off = _shard_offset(n_loc)
    p_loc = pods.capacity
    poff = _pod_offset(p_loc)

    # ONE pod-axis gather for the whole pass loop: gang counting, the
    # acceptance oracle and rollback flags are global over pods
    pods_f = _gather_pods(pods)
    g = gangs.capacity
    pre_ok = pre_enqueue_mask(pods_f, gangs)
    active = pods_f.valid & pre_ok                 # (P,)

    total = jnp.full(p_total, -1, jnp.int32)
    kept_so_far = jnp.zeros(p_total, bool)
    requested = st_local.node_requested            # (n_loc, R)
    cur_quota = quota
    pod_est_all = pod_estimates(pods_f, cfg)       # (P, R)
    est_local = jnp.zeros_like(st_local.node_usage)
    steps = jnp.int32(0)                           # the exact scans' trips

    for _ in range(passes):
        with jax.named_scope("gang_pass"):
            solve_st = st_local.replace(
                node_requested=requested,
                node_usage=st_local.node_usage + est_local,
                node_agg_usage=st_local.node_agg_usage + est_local)
            act_pods = pods_f.replace(valid=active)
            if solver == "batch":
                # selection runs on this tile's LOCAL pod rows against the
                # est-augmented local node tile; the winners ride the one
                # nodes-axis merge inside and a pod-axis gather after
                loc_active = jax.lax.dynamic_slice(active, (poff,), (p_loc,))
                pods_loc = pods.replace(valid=pods.valid & loc_active)
                ck_loc, cn_loc, _ = _local_select_body(
                    solve_st, pods_loc, cfg, k=k, strata=strata,
                    n_total=n_total)
                ck, cn = _gather_pods((ck_loc, cn_loc))
                a, _, _ = _rounds_local(
                    solve_st, act_pods, cur_quota, ck, cn,
                    rounds=rounds, n_total=n_total)
            else:
                a, _, _, scanned = _greedy_local(
                    solve_st, act_pods, cfg, cur_quota)
                steps = steps + scanned

            # rollback_failed_gangs, replicated flags + owner-local rebuild
            assigned = (a >= 0) & act_pods.valid
            counted = assigned | kept_so_far
            counts = _per_gang_counts(counted, pods_f.gang_id, g)
            gang_ok = (counts >= gangs.min_member) & gangs.valid
            ok = _group_ok(gang_ok, gangs)
            pod_gang = jnp.maximum(pods_f.gang_id, 0)
            keep = assigned & ((pods_f.gang_id < 0) | ok[pod_gang])
            failed = (pods_f.gang_id >= 0) & ~ok[pod_gang] & act_pods.valid
            final = jnp.where(keep, a, -1)

            loc = final - off
            own = keep & (loc >= 0) & (loc < n_loc)
            loc_c = jnp.clip(loc, 0, n_loc - 1)
            requested = requested.at[loc_c].add(
                jnp.where(own[:, None], pods_f.requests, 0))
            est_local = est_local.at[loc_c].add(
                jnp.where(own[:, None], pod_est_all, 0))
            if cur_quota is not None:
                cur_quota = charge_quota_batch(
                    cur_quota, pods_f.requests, pods_f.quota_id, keep,
                    pods_f.non_preemptible)
            total = jnp.where(keep, final, total)
            kept_so_far = kept_so_far | keep
            # next pass: still-unassigned pods stay in play, but rolled-back
            # gangs back off for the rest of the batch
            active = active & ~keep & ~failed

    return (total, st_local.replace(node_requested=requested), cur_quota,
            steps)


@lru_cache(maxsize=None)
def _gang_program(mesh, n_total, p_total, passes, solver, k, strata,
                  rounds):
    """Jitted shard_map gang program (see :func:`_select_program`)."""
    return jax.jit(jax.shard_map(
        partial(_gang_body, passes=passes, solver=solver, k=k,
                strata=strata, rounds=rounds, n_total=n_total,
                p_total=p_total),
        mesh=mesh, in_specs=(_NODES, _PODS, _REP, _REP, _REP),
        out_specs=(_REP, _NODES, _REP, _REP), check_vma=False))


def sharded_gang_assign(mesh, state, pods, cfg, gangs, quota=None,
                        passes: int = 2, solver: str = "greedy",
                        k: int = ba.CAND_K,
                        rounds: int = ba.SOLVE_ROUNDS,
                        spread_bits=ba.CAND_SPREAD_BITS):
    """``ops/gang.gang_assign`` over the 2-D mesh — the explicit
    shard_map twin of the GSPMD-placed gang path, for both per-pass
    engines (``solver="batch"`` propose/accept rounds and
    ``solver="greedy"``'s exact sequential scan).  Every default —
    including ``solver="greedy"`` — matches ``gang_assign``'s, and the
    candidate knobs match ``batch_assign``'s, so a drop-in swap of the
    entry point keeps acceptance decisions bit-identical to the
    single-device ``gang_assign`` (selection is recall-exact here, like
    every sharded entry).

    Returns (assignments, new_state, new_quota, stats) with the state
    node-sharded and ``stats`` the exact scans' ``ScanStats`` (None for
    ``solver="batch"``); requires the factored (selector-mask)
    feasibility form — a dense (P, N) ``pods.feasible`` cannot tile."""
    from koordinator_tpu.ops.assignment import ScanStats

    if solver not in ("greedy", "batch"):
        raise ValueError(f"unknown solver {solver!r}")
    if pods.feasible is not None:
        raise ValueError(
            "sharded_gang_assign requires the factored selector-mask "
            "feasibility form; a dense (P, N) feasible matrix does not "
            "tile over the 2-D mesh (build the batch with "
            "selector_mask, or keep the GSPMD gang path)")
    strata = (tuple(spread_bits) if isinstance(spread_bits, (tuple, list))
              else (spread_bits,))
    n_total = state.capacity
    check_shardable(n_total, mesh)
    check_pod_shardable(pods.capacity, mesh)
    fn = _gang_program(mesh, n_total, pods.capacity, passes, solver,
                       min(k, n_total), strata, rounds)
    a, new_state, new_quota, steps = fn(state, pods, cfg, gangs, quota)
    return (a, new_state, new_quota,
            ScanStats(steps=steps) if solver == "greedy" else None)


# koordlint: shape[state: NxR i32 nodes, reserve: NxR i32 nodes]
def sharded_forecast_gang_assign(mesh, state, reserve, pods, cfg, gangs,
                                 quota=None, passes: int = 2,
                                 solver: str = "greedy", k: int = ba.CAND_K,
                                 rounds: int = ba.SOLVE_ROUNDS,
                                 spread_bits=ba.CAND_SPREAD_BITS):
    """:func:`sharded_gang_assign` with the forecast-headroom reserve
    charged for the duration of the solve — the sharded twin of
    ``forecast/kernels.forecast_gang_assign``.

    The charge and release are elementwise over the node axis, so both
    stay on each shard's slice under the state's NamedSharding (the
    plane pins its reserve under the same placement); the inner solve
    is the unchanged shard_map program, so acceptance decisions are
    bit-identical to the single-device forecast entry."""
    charged = state.replace(node_requested=state.node_requested + reserve)
    a, new_state, new_quota, stats = sharded_gang_assign(
        mesh, charged, pods, cfg, gangs, quota, passes=passes,
        solver=solver, k=k, rounds=rounds, spread_bits=spread_bits)
    return a, new_state.replace(
        node_requested=new_state.node_requested - reserve), new_quota, stats


def sharded_greedy_assign(mesh, state, pods, cfg, quota=None):
    """``ops/assignment.greedy_assign`` over the mesh as one explicit
    shard_map kernel: the sequential scan keeps its exact pod order
    (there is no pod parallelism in a priority scan), node tensors are
    sharded, and each step's argmax merges over the nodes axis — no
    all-gather of the (P, N) problem.  Returns (assignments, new_state,
    new_quota) like the single-device entry."""
    if pods.feasible is not None:
        raise ValueError(
            "sharded_greedy_assign requires the factored selector-mask "
            "feasibility form (see sharded_gang_assign)")
    n_total = state.capacity
    check_shardable(n_total, mesh)
    check_pod_shardable(pods.capacity, mesh)
    return _greedy_program(mesh, n_total)(state, pods, cfg, quota)


# koordlint: shape[st_local: NxR i32 nodes]
def _greedy_body(st_local, pods, cfg, quota):
    pods_f = _gather_pods(pods)
    a, requested, new_quota, _ = _greedy_local(st_local, pods_f, cfg, quota)
    return a, st_local.replace(node_requested=requested), new_quota


@lru_cache(maxsize=None)
def _greedy_program(mesh, n_total):
    """Jitted shard_map greedy program (see :func:`_select_program`)."""
    return jax.jit(jax.shard_map(
        _greedy_body,
        mesh=mesh, in_specs=(_NODES, _PODS, _REP, _REP),
        out_specs=(_REP, _NODES, _REP), check_vma=False))


# ---------------------------------------------------------------------------
# Quality mode: the LP-relaxation packing solve over the nodes axis
# ---------------------------------------------------------------------------


# koordlint: shape[st_local: NxR i32 nodes]
def _lp_pack_body(st_local, pods, quota, cfg, *, n_total, ascent_iters,
                  rounding_iters):
    """Shard-local LP-pack body: the SAME ``quality/lp_pack._lp_core``
    the single-device entry runs, with the collectives live.  Scores
    and prices are shard-local columns; the per-pod argmax merges
    per-shard winners on the global integer (key, tb) scale and every
    acceptance decision is replicated — the union-of-bests and
    owner-psum exactness arguments of the greedy rounds apply term for
    term, and all arithmetic is integer, so shard counts can't perturb
    a single bit.

    On a 2-D mesh the LP twin COMPOSES by replicating the pod batch
    over the pods axis (in_spec ``P()``; the price-ascent re-bidding
    loop re-chooses every pod every iteration, so a pod split would put
    a pod-axis all-gather INSIDE the ascent loop — the exact pattern
    the koordlint corpus forbids).  Node work still shards 1/dn;
    docs/sharding.md's axis-sizing guidance says to spend devices on
    the nodes axis when quality mode dominates."""
    from koordinator_tpu.quality.lp_pack import _lp_core

    a, requested, new_quota, iters = _lp_core(
        st_local, pods, quota, cfg, n_total=n_total,
        ascent_iters=ascent_iters, rounding_iters=rounding_iters,
        axis=NODES_AXIS)
    return a, st_local.replace(node_requested=requested), new_quota, iters


@lru_cache(maxsize=None)
def _lp_pack_program(mesh, n_total, ascent_iters, rounding_iters):
    """Jitted shard_map LP program, memoized on (mesh, shape, bounds).

    The LP solve is a while-loop program an order of magnitude pricier
    to trace than the greedy passes; without the memo every direct call
    (the mesh-invariance sweeps, bench stages) re-traces it even at
    identical shapes.  ``Mesh`` hashes by (devices, axis names), so
    equal meshes built by different ``solver_mesh`` calls share the
    entry; the kit's own jit wrapper composes fine on top (nested jit
    inlines)."""
    return jax.jit(jax.shard_map(
        partial(_lp_pack_body, n_total=n_total,
                ascent_iters=ascent_iters,
                rounding_iters=rounding_iters),
        mesh=mesh, in_specs=(_NODES, _REP, _REP, _REP),
        out_specs=(_REP, _NODES, _REP, _REP), check_vma=False))


def sharded_lp_pack_assign(mesh, state, pods, cfg, quota=None,
                           ascent_iters: int | None = None,
                           rounding_iters: int | None = None):
    """``quality/lp_pack.lp_pack_assign`` over the mesh's nodes axis.

    Bit-identical to the single-device LP solve at every mesh shape
    (tests/test_quality.py sweeps shard counts; the 2-D sweep rides
    tests/test_sharded_solve.py): returns (assignments, new_state,
    new_quota, iters) with the state node-sharded like the greedy
    sharded passes.  Pod tensors replicate over the pods axis — see
    :func:`_lp_pack_body` for why that is the composition rule here."""
    from koordinator_tpu.quality import lp_pack as lp

    n_total = state.capacity
    check_shardable(n_total, mesh)
    fn = _lp_pack_program(
        mesh, n_total,
        lp.ASCENT_ITERS if ascent_iters is None else ascent_iters,
        lp.ROUNDING_ITERS if rounding_iters is None else rounding_iters)
    return fn(state, pods, quota, cfg)
