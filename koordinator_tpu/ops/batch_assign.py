"""Batch-parallel assignment: propose/accept rounds instead of an O(P) scan.

``greedy_assign`` (ops/assignment.py) is the exact sequential solver — one
dependent loop step per placeable pod, 50k at the north-star shape.  This
module is the throughput path: the whole pending queue lands in a handful of
data-parallel rounds.

    1. ONE fused Filter+Score pass over the (P, N) problem (same kernels as
       ``score_pods``), with a per-pod rotated tie-break so identical pods
       spread over equal-scored nodes instead of stampeding one argmax;
    2. ``lax.top_k`` -> each pod's k best candidate nodes, (P, k);
    3. K propose/accept rounds on the small (P, k) tensors: every active pod
       proposes its best candidate that still fits, conflicts are resolved by
       a segmented prefix-sum over requests in priority order (higher-priority
       pods win a contended node, exactly one device-wide sort per round), and
       elastic-quota headroom is enforced by the same prefix trick per
       ancestor level of the quota chain.

Semantics vs the reference / greedy_assign:
- priority order in conflicts matches the scheduler queue order
  (priority desc, stable) — the prefix acceptance is the tensor analog of
  higher-priority pods going through scheduleOne first;
- capacity and quota feedback happen per round (snapshot granularity) rather
  than per pod: scores are not recomputed between two pods of the same round,
  like the upstream parallel Filter/Score over one snapshot;
- a pod only ever considers its top-k candidates; under extreme contention a
  pod can go unassigned in this solve even though some node below its top-k
  would fit (it retries next scheduler round).  k and the round count bound
  the approximation.

Reference parity anchors: scoring pipeline per cmd/koord-scheduler/main.go
plugin registry; quota admission per elasticquota/plugin.go:256-304; the
conflict rule mirrors upstream queue ordering (priority, then FIFO).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from flax import struct

from koordinator_tpu.ops import deviceshare, numa
from koordinator_tpu.ops.assignment import (
    Grants,
    ScoringConfig,
    apply_grants,
    empty_grants,
    keep_planes,
    score_pods,
)
from koordinator_tpu.quota.admission import (
    QuotaDeviceState,
    charge_quota_batch,
    quota_admission_mask,
)
from koordinator_tpu.state.cluster_state import ClusterState, PodBatch

#: tie-break field width of the PACKED ranking key: node index occupies the
#: low bits, the quantized score the high bits, of one int32
_TB_BITS = 15
_SCORE_CLIP = (1 << 30 - _TB_BITS) - 1

#: node capacities up to this fit the packed single-int32 key regime
#: (score and rotated tie-break in one word, one ``lax.top_k``).  Larger
#: capacities switch to the WIDE regime: the ranking key carries the
#: quantized score alone and the rotated tie-break rides a second int32,
#: compared lexicographically (a two-operand ``lax.sort`` at selection,
#: a two-stage argmax in the rounds).  The packed regime is bit-identical
#: to the historical behavior; the wide regime never aliases because
#: nothing is packed.
PACKED_NODE_CAPACITY = 1 << _TB_BITS

#: hard node-capacity ceiling of the solver: node rows index as
#: nonnegative int32 and the tie-break rotation arithmetic
#: (``rot_id * 7919`` against a node id) must stay inside int32.  The
#: old 2**15 packing wall is gone — past it the wide two-key regime
#: ranks exactly — so this guard is about integer width, not packing.
MAX_NODE_CAPACITY = 1 << 30


def check_node_capacity(n: int) -> None:
    """Raise if a node capacity exceeds the ranking key's ceiling."""
    if n > MAX_NODE_CAPACITY:
        raise ValueError(
            f"node capacity {n} exceeds the batched solver's ranking-key "
            f"ceiling of {MAX_NODE_CAPACITY} (= 2**30): node rows must "
            "index as nonnegative int32 and the rotated tie-break "
            "arithmetic must not overflow.  Node-axis mesh sharding "
            "(parallel/sharded.py) spreads the per-device footprint but "
            "keys stay global-int32; a cluster past 2**30 nodes needs a "
            "64-bit key carrier.")


def _packed_regime(n_total: int) -> bool:
    """True when ``n_total`` node rows fit the packed int32 key."""
    return n_total <= PACKED_NODE_CAPACITY


def _ranked_scores(
    scores: jnp.ndarray, feasible: jnp.ndarray, spread_bits: int = 0,
    rot_id: jnp.ndarray | None = None,
    node_ids: jnp.ndarray | None = None,
    n_total: int | None = None,
) -> jnp.ndarray:
    """(P, N) int32 ranking key: score in the high bits, a per-pod rotated
    node index in the low bits.  Equal-scored nodes order differently for
    every pod, so homogeneous pods fan out instead of all picking node 0
    (selectHost randomizes among maxima upstream; rotation is the
    deterministic equivalent).

    ``spread_bits`` quantizes the score into buckets of ``2**spread_bits``
    before ranking.  With exact scores, every pod ranks nodes near-identically
    and the whole queue's top-k candidate sets collapse onto the same few
    nodes — at 50k pods x 10k nodes that strands >90% of a schedulable queue.
    Bucketing widens the tie groups so the rotation fans candidates over ALL
    near-best nodes; the score sacrifice is bounded by the bucket width
    (upstream's selectHost already treats equal-enough scores as
    interchangeable: defaultPodTopologySpread jitter, selectHost randomness).

    ``rot_id`` is the per-pod rotation identity (``PodBatch.rot_id``;
    defaults to the batch row index).  Keys are a pure function of
    (rot_id, node id, score) — independent of the pod's batch ROW — which
    is what lets chunked reductions and the incremental candidate cache
    reproduce any single row bit-for-bit.  ``node_ids``/``n_total`` score
    a gathered COLUMN SUBSET (the dirty-node refresh): the tie-break uses
    the nodes' GLOBAL ids modulo the full capacity, so a subset column's
    key equals the same node's key in a full (P, N) pass.
    """
    return _rank_parts(scores, feasible, spread_bits, rot_id,
                       node_ids, n_total)[0]


# koordlint: shape[ret0: PxN i32 -1..1073741823, ret1: PxN i32 0..1073741823]
def _rank_parts(
    scores: jnp.ndarray, feasible: jnp.ndarray, spread_bits: int = 0,
    rot_id: jnp.ndarray | None = None,
    node_ids: jnp.ndarray | None = None,
    n_total: int | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(key, tb) pair behind :func:`_ranked_scores`.

    Packed regime (``n_total <= PACKED_NODE_CAPACITY``): ``key`` is the
    historical single int32 ``(q << _TB_BITS) | tb`` and already encodes
    the tie-break.  Wide regime: ``key`` is the quantized score alone and
    callers break ties lexicographically with ``tb`` (``_topk_by_rank``,
    the rounds' two-stage argmax).  ``tb`` is returned in both regimes so
    shard-local selections can always merge on the same (key, tb) scale.
    """
    p, n = scores.shape
    n_total = n if n_total is None else n_total
    check_node_capacity(n_total)
    if rot_id is None:
        rot_id = jnp.arange(p, dtype=jnp.int32)
    rot = (rot_id.astype(jnp.int32) * 7919)[:, None]
    ids = (jnp.arange(n, dtype=jnp.int32)[None, :] if node_ids is None
           else node_ids.astype(jnp.int32)[None, :])
    tb = (ids - rot) % n_total
    # invert so the SMALLEST rotated distance ranks highest among ties
    tb = (n_total - 1) - tb
    q = jnp.clip(scores, 0, _SCORE_CLIP) >> spread_bits
    key = ((q << _TB_BITS) | tb) if _packed_regime(n_total) else q
    return jnp.where(feasible, key, -1), tb


def _candidate_tb(node: jnp.ndarray, rot_id: jnp.ndarray,
                  n_total: int) -> jnp.ndarray:
    """The (P, k) rotated tie-break of cached candidate node rows — the
    same pure function of (rot_id, node) that :func:`_rank_parts` packs
    (packed regime) or returns alongside (wide regime)."""
    rot = (rot_id.astype(jnp.int32) * 7919)[:, None]
    return (n_total - 1) - ((node - rot) % n_total)


# koordlint: shape[score: Pxk i32 -1..32767]
def _candidate_keys(score: jnp.ndarray, node: jnp.ndarray,
                    rot_id: jnp.ndarray, spread_bits: int,
                    n_total: int) -> jnp.ndarray:
    """Ranking key recomputed from a CACHED candidate's raw clipped score
    and node row — bit-identical to the :func:`_ranked_scores` key of the
    same (pod, node) pair, so merged and freshly-selected candidates rank
    on one scale.  ``score < 0`` marks an invalid slot."""
    q = score >> spread_bits
    if _packed_regime(n_total):
        key = (q << _TB_BITS) | _candidate_tb(node, rot_id, n_total)
    else:
        key = q
    return jnp.where(score >= 0, key, -1)


def _topk_by_rank(key: jnp.ndarray, tb: jnp.ndarray, k: int,
                  n_total: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Exact per-row top-k columns by (key, tb) rank, descending —
    ``lax.top_k`` when the packed key already encodes the tie-break, a
    two-operand lexicographic ``lax.sort`` in the wide regime.  Returns
    (key_sel, col_idx) like ``lax.top_k``.  Rank pairs of feasible
    columns are unique per row (tb is a permutation of node ids), so the
    result is order-deterministic in both regimes."""
    if _packed_regime(n_total):
        return jax.lax.top_k(key, k)
    n = key.shape[-1]
    cols = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), key.shape)
    key_s, _, idx_s = jax.lax.sort((key, tb, cols), num_keys=2)
    sl = slice(n - k, None)
    return (jnp.flip(key_s[..., sl], -1).astype(key.dtype),
            jnp.flip(idx_s[..., sl], -1))


def _prefix_accept(
    choice: jnp.ndarray,     # (P,) int32 proposed segment (node/quota row)
    requests: jnp.ndarray,   # (P, R) int32
    free: jnp.ndarray,       # (S, R) int32 segment headroom
    order: jnp.ndarray,      # (P,) priority-descending pod order
    active: jnp.ndarray,     # (P,) bool — proposers this round
) -> jnp.ndarray:
    """(P,) bool: cumulative request per segment (taken in ``order`` among
    active proposers) fits the segment's headroom, counting the pod itself.

    This is the round's conflict resolution: the tensor equivalent of
    higher-priority pods passing through the scheduling cycle first.

    Fast path: when NO segment is oversubscribed (every segment's total
    proposed request fits its headroom — the common case from round 2 on,
    once the first round's land grab settles), every active proposer's
    prefix trivially fits, so the answer is ``active`` and the device-wide
    stable sort is skipped via ``lax.cond``.  One cheap segment-sum pays
    for the detection; the sorted path below remains the general case and
    the single source of truth for contended rounds.
    """
    s = free.shape[0]
    choice_free = jnp.where(
        active[:, None], free[jnp.clip(choice, 0, s - 1)], 0)
    return _prefix_accept_choice(choice, requests, choice_free, s,
                                 order, active)


def _prefix_accept_choice(
    choice: jnp.ndarray,       # (P,) int32 proposed segment
    requests: jnp.ndarray,     # (P, R)
    choice_free: jnp.ndarray,  # (P, R) headroom of each pod's OWN segment
    num_segments: int,
    order: jnp.ndarray,
    active: jnp.ndarray,
) -> jnp.ndarray:
    """The choice-indexed core of :func:`_prefix_accept`: the segment
    headroom arrives pre-gathered per pod instead of as an (S, R) table.
    This is the form the node-sharded rounds reuse — each shard psums
    the headroom of the candidates it owns into ``choice_free``, then
    every shard runs this replicated decision identically (see
    parallel/sharded.py for the exactness argument)."""
    s = num_segments
    seg = jnp.where(active, choice, s)            # inactive -> overflow row
    req_act = jnp.where(active[:, None], requests, 0)
    totals = jax.ops.segment_sum(req_act, seg, num_segments=s + 1)
    # a segment is oversubscribed iff one of its own proposers sees its
    # total exceed the (shared) headroom — same predicate as scanning
    # the (S, R) table, evaluated through the pods that propose there
    contended = jnp.any(active[:, None] & (totals[seg] > choice_free))

    def fast(_):
        # total per segment fits => every within-segment prefix fits
        return active

    def slow(_):
        return _prefix_accept_sorted_choice(seg, requests, choice_free,
                                            order, active)

    return jax.lax.cond(contended, slow, fast, None)


def _prefix_accept_sorted(seg, requests, free, order, active):
    """The general contended-round path over an (S, R) headroom table:
    kept as the spec/test surface; delegates to the choice-indexed core."""
    r = requests.shape[1]
    free_pad = jnp.concatenate([free, jnp.zeros((1, r), free.dtype)])
    return _prefix_accept_sorted_choice(seg, requests, free_pad[seg],
                                        order, active)


def _prefix_accept_sorted_choice(seg, requests, choice_free, order, active):
    """Contended-round acceptance: stable sort groups segments in
    priority order, a segmented prefix-sum checks cumulative fit against
    each pod's own-segment headroom."""
    p, r = requests.shape
    seg_o = seg[order]
    req_o = jnp.where(active[order][:, None], requests[order], 0)
    free_o = choice_free[order]
    pos = jnp.argsort(seg_o, stable=True)         # group segments, keep order
    seg_s = seg_o[pos]
    req_s = req_o[pos]
    cum = jnp.cumsum(req_s, axis=0)
    excl = cum - req_s
    is_start = jnp.concatenate(
        [jnp.ones((1,), bool), seg_s[1:] != seg_s[:-1]]
    )
    # propagate each segment's starting cumulative value (cum is
    # non-decreasing, so a running max of start markers yields the most
    # recent segment start)
    base = jax.lax.associative_scan(
        jnp.maximum, jnp.where(is_start[:, None], excl, -1), axis=0
    )
    prefix = cum - base                           # within-segment incl. self
    fits = jnp.all((prefix <= free_o[pos]) | (req_s == 0), axis=-1)
    out = jnp.zeros(p, bool).at[order[pos]].set(fits)
    return out & active


def _quota_prefix_accept(
    quota: QuotaDeviceState,
    requests: jnp.ndarray,
    pods: PodBatch,
    order: jnp.ndarray,
    active: jnp.ndarray,
) -> jnp.ndarray:
    """(P,) bool: within-round quota headroom conflict resolution.

    For every ancestor level of the quota chain, the cumulative masked
    request of this round's proposers must fit the ancestor's headroom
    (admission checks a static headroom; this prevents one round from
    collectively overshooting it).  Non-preemptible pods additionally
    prefix-check min headroom at their own quota.
    """
    qid = jnp.maximum(pods.quota_id, 0)
    has_quota = pods.quota_id >= 0
    checked = quota.checked[qid]                       # (P, R)
    req_m = jnp.where(checked, requests, 0)
    ok = jnp.ones(pods.capacity, bool)
    depth = quota.chain.shape[1]
    for d in range(depth):
        anc = quota.chain[qid, d]                      # (P,)
        act_d = active & has_quota & (anc >= 0)
        acc = _prefix_accept(
            jnp.maximum(anc, 0), req_m, quota.headroom, order, act_d
        )
        ok = ok & (acc | ~act_d)
    np_act = active & has_quota & pods.non_preemptible
    np_acc = _prefix_accept(qid, req_m, quota.min_headroom, order, np_act)
    ok = ok & (np_acc | ~np_act)
    return ok | ~has_quota


@struct.dataclass
class _RoundCarry:
    requested: jax.Array      # (N, R)
    assignments: jax.Array    # (P,)
    active: jax.Array         # (P,)
    quota: QuotaDeviceState | None
    #: the device stage's share of the carry, None for a state without
    #: devices: the per-device free tensor beside ``requested``, and what
    #: was granted (and lost) so far
    dev_free: jax.Array | None = None       # (N, D, 2)
    #: the cpuset stage's: the CPU plane's ``held`` beside it
    cpu_held: jax.Array | None = None       # (N, C)
    grants: Grants | None = None


#: candidate-selection strategies for ``select_candidates``:
#: - "exact":  XLA score + exact ``lax.top_k`` on the int ranking key
#: - "approx": XLA score + ``lax.approx_max_k`` on a 24-bit float key
#:             (~0.95 recall on TPU; the CPU lowering is exact, but the
#:             float-key quantization is exercised on every backend)
#: - "chunked": the approx reduction over pod CHUNKS via ``lax.map`` —
#:             bit-identical rows to "approx" (global row offsets feed the
#:             rotation), but peak memory is (chunk, N), not (P, N): at
#:             the 50k x 10,240 shape the unchunked path materializes
#:             ~2 GB per (P, N) tensor (scores, feasible, ranking keys),
#:             the chunked path ~160 MB per (4096, N) block
#: - "chunked_exact": the chunked schedule with ``lax.top_k`` on the
#:             exact int keys instead of ``approx_max_k`` on the float
#:             keys — bit-identical rows to "exact" at chunked peak
#:             memory.  The TPU fallback when the measured approx_max_k
#:             recall strands pods (bench_recall.py's decision rule):
#:             the only other recall-exact option materializes (P, N)
#: - "auto":   "approx" on TPU, "exact" elsewhere
#:             (``resolve_candidate_method``, the rule's one home)
CANDIDATE_METHODS = ("auto", "exact", "approx", "chunked",
                     "chunked_exact")

#: the candidate parameters' one home: every entry below, their twins in
#: parallel/sharded.py and the SolverKit default to these, so the full,
#: incremental, tenant-axis and sharded rounds solve the same problem
CAND_K = 32                 # candidates kept per pod
CAND_SPREAD_BITS = (5, 15)  # stratified quantization (select_candidates)
SOLVE_ROUNDS = 12           # propose/accept rounds per pass


def resolve_candidate_method(method: str) -> str:
    """The one home of the ``"auto"`` rule: the concrete candidate method
    a requested one runs as on this process's backend.  The scheduler's
    incremental path and the tenant-axis program key their caches on the
    resolved name, so they resolve through here too."""
    if method not in CANDIDATE_METHODS:
        raise ValueError(f"unknown candidate method {method!r}; "
                         f"one of {CANDIDATE_METHODS}")
    if method == "auto":
        return "approx" if jax.default_backend() == "tpu" else "exact"
    return method


def batch_assign(
    state: ClusterState,
    pods: PodBatch,
    cfg: ScoringConfig,
    quota: QuotaDeviceState | None = None,
    k: int = CAND_K,
    rounds: int = SOLVE_ROUNDS,
    spread_bits=CAND_SPREAD_BITS,
    method: str = "auto",
    with_grants: bool = False,
):
    """Assign a pending batch in data-parallel propose/accept rounds.

    Same signature/returns as ``greedy_assign``: (assignments, new_state,
    new_quota), and the device grants after them with ``with_grants``.
    assignments is (P,) int32, -1 = unassigned.

    ``spread_bits`` controls the candidate-diversity/score trade-off (see
    ``select_candidates``): an int ranks all k candidates by one quantized
    key; the default STRATIFIED ``(5, 15)`` splits k between a
    score-faithful stratum (buckets of 32 — measured at or above exact
    greedy's mean chosen score at 2k nodes x 10k pods) and a pure-rotation
    coverage stratum, because a single sb=5 key strands 14% of a fully
    schedulable 50k-pod queue at 10,240 nodes once the top score band
    fills (docs/solve_quality.md "Stratified candidates at shape": sb=5
    86.4% assigned, stratified and deep-spread variants 100%).

    ``method`` picks the candidate-selection strategy (CANDIDATE_METHODS);
    every method is force-selectable on every backend so CI can cover the
    TPU-serving branches on CPU.  Candidate recall is approximate for
    "approx"/"chunked"; acceptance always enforces fit and quota exactly.
    """
    cand_key, cand_node = select_candidates(
        state, pods, cfg, k=k,
        spread_bits=spread_bits, method=method)
    return _assign_rounds(state, pods, quota, cand_key, cand_node, rounds,
                          with_grants=with_grants)


def select_candidates(
    state: ClusterState,
    pods: PodBatch,
    cfg: ScoringConfig,
    k: int = CAND_K,
    spread_bits=CAND_SPREAD_BITS,
    method: str = "auto",
    with_scores: bool = False,
):
    """(cand_key, cand_node), each (P, k): the candidate-selection stage of
    ``batch_assign``, exposed separately so profiling can time it apart
    from the propose/accept rounds.  See CANDIDATE_METHODS.

    ``spread_bits`` may be an int (one quantization depth) or a tuple of
    depths — STRATIFIED selection: k splits evenly across the strata, each
    stratum picks its share by its own quantized ranking key, and the
    first stratum's key orders all candidates inside the rounds.  The
    default ``(5, 15)`` pairs a score-faithful stratum (buckets of 32 —
    best placement quality; measured above exact greedy's mean chosen
    score at 2k nodes) with a pure-rotation coverage stratum (score-free
    consecutive-window candidates) — at the 50k x 10,240 north-star shape
    a single sb=5 key strands 14% of a fully-schedulable queue when the
    top score band fills, while the coverage stratum guarantees every pod
    k/2 uniformly-spread fallbacks (measured: 100% assigned).  Duplicate
    nodes between strata just idle a slot.  Scoring runs ONCE regardless
    of strata count; only the cheap top-k reduction repeats.

    ``with_scores=True`` additionally returns the selected slots' raw
    clipped composite scores, (P, k) int32 with -1 for invalid slots —
    the persistent form the incremental candidate cache needs to
    recompute any stratum's ranking key without a full rescore."""
    method = resolve_candidate_method(method)
    strata = (spread_bits if isinstance(spread_bits, (tuple, list))
              else (spread_bits,))
    if method in ("chunked", "chunked_exact"):
        return _chunked_candidates(state, pods, cfg, k=k, strata=strata,
                                   method=method, with_scores=with_scores)
    with jax.named_scope("score"):
        scores, feasible = score_pods(state, pods, cfg)
    with jax.named_scope("select"):
        return _reduce_candidates(scores, feasible, strata,
                                  min(k, scores.shape[1]), method,
                                  pods.rot_id, with_scores=with_scores)


def _reduce_candidates(scores, feasible, strata, k: int, method: str,
                       rot_id=None, with_scores: bool = False,
                       node_ids=None, n_total: int | None = None):
    """The (scores, feasible) -> (cand_key, cand_node) reduction shared by
    the whole-batch, chunked and shard-local paths.  ``node_ids``/
    ``n_total`` score a gathered COLUMN SUBSET (a shard's local columns):
    keys use global node ids and ``cand_node`` returns global rows."""
    n_total = scores.shape[1] if n_total is None else n_total
    order_key, order_tb = _rank_parts(scores, feasible, strata[0], rot_id,
                                      node_ids, n_total)
    splits = _stratum_splits(k, len(strata))
    nodes = []
    for sb, k_i in zip(strata, splits):
        if k_i == 0:
            continue
        key, tb = ((order_key, order_tb) if sb == strata[0]
                   else _rank_parts(scores, feasible, sb, rot_id,
                                    node_ids, n_total))
        if method in ("approx", "chunked") and k_i < key.shape[1]:
            # TPU-optimized partial reduction. approx_max_k needs a float
            # key exact within float32's 24-bit mantissa, so candidates
            # are chosen by the quantized score plus as many HIGH bits of
            # the rotated tie-break as fit (high bits keep the
            # closest-after-rotation ordering that fans pods out; low
            # bits would scramble it); the exact int keys are then
            # gathered for in-round ordering.  Candidate RECALL is
            # approximate (~recall_target on TPU; the CPU lowering of
            # approx_max_k is exact, so CPU recall loss comes only from
            # the float-key quantization).  Acceptance still enforces fit
            # and quota exactly.
            score_bits = (30 - _TB_BITS) - sb   # quantized field width
            if _packed_regime(n_total):
                shift = min(_TB_BITS, max(24 - score_bits, 0))
                fkey = jnp.where(
                    key >= 0,
                    ((key >> _TB_BITS) << shift
                     | (key & ((1 << _TB_BITS) - 1)) >> (_TB_BITS - shift)
                     ).astype(jnp.float32),
                    -1.0)
            else:
                # wide regime: q rides the float key's high integer bits,
                # the top tie-break bits fill the rest of the 24-bit
                # mantissa (q < 2**score_bits keeps the sum exact)
                tb_bits = max((n_total - 1).bit_length(), 1)
                shift = max(24 - score_bits, 0)
                fkey = jnp.where(
                    key >= 0,
                    # koordlint: ignore[dtype-regime] -- trace-time Python int shift (arbitrary precision) feeding a float32 scale, never int32 array math
                    key.astype(jnp.float32) * float(1 << shift)
                    + (tb >> max(tb_bits - shift, 0)).astype(jnp.float32),
                    -1.0)
            _, idx = jax.lax.approx_max_k(
                fkey, k_i, recall_target=0.95, aggregate_to_topk=True)
            nodes.append(idx.astype(jnp.int32))
        else:
            _, idx = _topk_by_rank(key, tb, k_i, n_total)
            nodes.append(idx)
    cand_cols = jnp.concatenate(nodes, axis=1) if len(nodes) > 1 else nodes[0]
    # the first stratum's key orders every candidate in the rounds, so a
    # coverage-stratum node competes on the same score scale (gathering
    # also yields -1 for infeasible slots of short candidate lists)
    cand_key = jnp.take_along_axis(order_key, cand_cols, axis=1)
    cand_node = (cand_cols if node_ids is None
                 else node_ids.astype(jnp.int32)[cand_cols])
    if with_scores:
        raw = jnp.take_along_axis(
            jnp.clip(scores, 0, _SCORE_CLIP), cand_cols, axis=1)
        return cand_key, cand_node, jnp.where(cand_key >= 0, raw, -1)
    return cand_key, cand_node


#: pod-chunk width for method="chunked": peak score memory is
#: (CANDIDATE_CHUNK, N) — 4096 x 10,240 x int32 = 160 MB at the
#: north-star shape, vs ~2 GB per (P, N) tensor unchunked
CANDIDATE_CHUNK = 4096


def _chunked_candidates(state, pods, cfg, k: int, strata,
                        chunk: int = CANDIDATE_CHUNK,
                        method: str = "chunked",
                        with_scores: bool = False):
    """The chunked reduction over pods: ``lax.map`` scores one
    (chunk, N) block at a time and reduces it to (chunk, k) before the
    next block's scores exist, so no (P, N) tensor is ever materialized.
    Rows are bit-identical to ``method="approx"`` (or, for
    ``method="chunked_exact"``, to ``method="exact"``) — scoring,
    ranking (per-pod rot_id) and the per-row reduction are all
    row-independent; chunking only changes the execution schedule."""
    p = pods.capacity
    k = min(k, state.capacity)
    chunk = min(chunk, p)   # a small batch must not score 4096-row pads
    n_chunks = -(-p // chunk)
    padded = n_chunks * chunk

    def pad_rows(a):
        # every PodBatch field is per-pod along axis 0 (the compact()
        # invariant), so the whole pytree pads uniformly; zero/False
        # padding means invalid rows, which reduce to key -1
        pad_width = [(0, padded - p)] + [(0, 0)] * (a.ndim - 1)
        return jnp.pad(a, pad_width)

    stacked = jax.tree.map(pad_rows, pods)

    def reshape_rows(a):
        return (None if a is None
                else a.reshape((n_chunks, chunk) + a.shape[1:]))

    def body(sub):
        with jax.named_scope("score"):
            scores, feasible = score_pods(state, sub, cfg)
        with jax.named_scope("select"):
            return _reduce_candidates(scores, feasible, strata, k,
                                      method, sub.rot_id,
                                      with_scores=with_scores)

    sub_batches = jax.tree.map(reshape_rows, stacked)
    out = jax.lax.map(body, sub_batches)
    return tuple(a.reshape(padded, -1)[:p] for a in out)


def _stratum_splits(k: int, n: int) -> list[int]:
    """Split k as evenly as possible over n strata (first strata get the
    remainder)."""
    base, rem = divmod(k, n)
    return [base + (1 if i < rem else 0) for i in range(n)]


def _choose_candidate(cand_key, cand_tb, fits):
    """(P,) column of each pod's best FITTING candidate by (key, tb)
    rank.  The packed key encodes the tie-break (``cand_tb`` is None);
    the wide regime runs a two-stage argmax — max key, then max tb among
    the key ties — which equals the lexicographic rank because rank
    pairs of distinct nodes are unique per pod."""
    masked = jnp.where(fits, cand_key, -1)
    if cand_tb is None:
        return jnp.argmax(masked, axis=1)
    best_key = jnp.max(masked, axis=1, keepdims=True)
    return jnp.argmax(
        jnp.where(fits & (masked == best_key), cand_tb, -1), axis=1)


#: rows a trip of the plane stages' Reserve loop grants on, at most: the
#: pods in turn beyond them wait one trip (:func:`_plane_accept`)
ACCEPT_BLOCK = 256


def _plane_accept(grant, apply, book, waiting, choice, order_pos, cols,
                  width):
    """A plane stage's Reserve for one round's accepted proposals: every
    node serves the pods accepted onto it one at a time, in priority
    order, each against what the ones before it left, so two pods of one
    round never share a grant.  A trip's turn is each node's first
    waiting pod (the smallest ``order_pos``); the first ``width`` pods in
    turn, by index, are granted on their own node rows, and the others
    keep waiting.  Those are on distinct nodes, each with every pod ahead
    of it there served, so each is its node's turn again next trip and
    the grants are the same as in one trip over all of them.

    ``grant(book, pods, rows)`` is the plane's grant for the (W,) pods,
    each on its (W,) node row of ``book``: ((W, cols) selection, (W,)
    ok); ``apply(book, pods, rows, sel)`` takes the selections off the
    book.  Returns (book, (P, cols) selection, (P,) granted, () rows
    granted on: trips x ``width``); a waiting pod that is not granted
    lost its race."""
    p, n = choice.shape[0], book.shape[0]

    def cond(c):
        return jnp.any(c[0])

    def body(c):
        waiting, book, selection, granted, rows_done = c
        seg = jnp.where(waiting, choice, n)
        first = jax.ops.segment_min(
            jnp.where(waiting, order_pos, p), seg, num_segments=n + 1)
        turn = waiting & (order_pos == first[seg])
        # the block's pods, then P for each empty slot: its grant is
        # masked off, and its writes by pod index are dropped
        pods = jnp.nonzero(turn, size=width, fill_value=p)[0]
        safe = jnp.minimum(pods, p - 1)
        rows = choice[safe]
        sel, ok = grant(book, safe, rows)
        ok = ok & (pods < p)
        sel = sel & ok[:, None]
        return (waiting.at[pods].set(False, mode="drop"),
                apply(book, safe, rows, sel),
                selection.at[pods].set(sel, mode="drop"),
                granted.at[pods].set(ok, mode="drop"),
                rows_done + width)

    _, book, selection, granted, rows_done = jax.lax.while_loop(
        cond, body,
        (waiting, book, jnp.zeros((p, cols), bool), jnp.zeros(p, bool),
         jnp.int32(0)))
    return book, selection, granted, rows_done


def _device_accept(dev, dev_free, dreq, choice, accept, order_pos, width):
    """DeviceShare Reserve (:func:`_plane_accept`) over the per-device
    free tensor: (dev_free, (P, D) selection, (P,) granted, () rows)."""
    usable = dev.valid & dev.healthy

    def grant(free, pods, rows):
        return deviceshare.grant_rows(
            free[rows], dev.total[rows], usable[rows], dev.group[rows],
            jax.tree.map(lambda a: a[pods], dreq))

    def apply(free, pods, rows, sel):
        return free.at[rows].add(
            -(sel[:, :, None] * dreq.ask[pods][:, None, :]))

    return _plane_accept(grant, apply, dev_free, accept & dreq.wants,
                         choice, order_pos, dev.shape[1], width)


def _cpuset_accept(cpus, held, creq, choice, accept, order_pos, width):
    """NodeNUMAResource Reserve (:func:`_plane_accept`) over the CPU
    plane's ``held``: (held, (P, C) selection, (P,) granted, () rows)."""

    def grant(held, pods, rows):
        on = jax.tree.map(lambda a: a[rows], cpus.replace(held=held))
        return numa.grant_cpu_rows(
            on, on.held, jax.tree.map(lambda a: a[pods], creq))

    def apply(held, pods, rows, sel):
        return held.at[rows].add(sel * creq.unit[pods][:, None])

    return _plane_accept(grant, apply, held, accept & creq.wants, choice,
                         order_pos, cpus.shape[1], width)


@jax.named_scope("assign_rounds")
def _assign_rounds(state, pods, quota, cand_key, cand_node, rounds,
                   with_grants: bool = False):
    """The shared propose/accept stage over (P, k) candidates.  The
    ``named_scope`` stage names here and below are metadata only: they
    name the device ops in a profiler trace and change no operand.

    Over a state with devices a proposal also needs a device fit at its
    candidate and, once accepted on the node's aggregate rows, a grant
    (:func:`_device_accept`); without one it is not accepted and
    proposes again next round, as a pod that lost a capacity race does.
    Over a state with CPUs a pinned proposal needs a cpuset fit and a
    grant the same way (:func:`_cpuset_accept`, after the devices: a pod
    that loses its cores gives its devices back).  Both grant in blocks
    of at most ``ACCEPT_BLOCK`` pods a trip.  ``with_grants``
    appends the :class:`~koordinator_tpu.ops.assignment.Grants` to
    (assignments, state, quota)."""
    cand_valid = cand_key >= 0
    dev = state.devices
    dreq = (None if dev is None
            else deviceshare.pod_device_requests(pods.requests))
    cpus = state.cpus
    creq = None if cpus is None else numa.pod_cpu_requests(pods)
    cand_tb = (None if _packed_regime(state.capacity)
               else _candidate_tb(cand_node, pods.rot_id, state.capacity))

    order = jnp.lexsort((jnp.arange(pods.capacity), -pods.priority))
    active0 = pods.valid & jnp.any(cand_valid, axis=1)

    carry = _RoundCarry(
        requested=state.node_requested,
        assignments=jnp.full(pods.capacity, -1, jnp.int32),
        active=active0,
        quota=quota,
    )
    if dev is not None or cpus is not None:
        # each pod's place in the priority order, for the plane stages
        order_pos = jnp.zeros(pods.capacity, jnp.int32).at[order].set(
            jnp.arange(pods.capacity, dtype=jnp.int32))
        width = min(pods.capacity, ACCEPT_BLOCK)
        carry = carry.replace(
            dev_free=None if dev is None else dev.free,
            cpu_held=None if cpus is None else cpus.held,
            grants=empty_grants(state, pods.capacity))

    def round_body(i, c: _RoundCarry) -> _RoundCarry:
        with jax.named_scope("propose"):
            free = jnp.where(
                state.node_valid[:, None],
                state.node_allocatable - c.requested, 0
            )
            # each pod's best candidate whose node still fits its request
            cand_free = free[cand_node]                    # (P, k, R)
            fits = jnp.all(
                (pods.requests[:, None, :] <= cand_free)
                | (pods.requests[:, None, :] == 0),
                axis=-1,
            ) & cand_valid
            if dev is not None:
                fits = fits & deviceshare.candidate_device_fit(
                    dev, c.dev_free, dreq, cand_node)
            if cpus is not None:
                fits = fits & numa.candidate_cpuset_fit(
                    cpus, c.cpu_held, creq, cand_node)
            best = _choose_candidate(cand_key, cand_tb, fits)
            has = jnp.take_along_axis(fits, best[:, None], axis=1)[:, 0]
            choice = jnp.take_along_axis(
                cand_node, best[:, None], axis=1)[:, 0]

        act = c.active & has
        if c.quota is not None:
            act = act & quota_admission_mask(
                c.quota, pods.requests, pods.quota_id, pods.non_preemptible
            )
        with jax.named_scope("prefix_accept"):
            accept = _prefix_accept(choice, pods.requests, free, order, act)
        if c.quota is not None:
            with jax.named_scope("quota_accept"):
                accept = accept & _quota_prefix_accept(
                    c.quota, pods.requests, pods, order, act
                )
        dev_free, cpu_held, grants = c.dev_free, c.cpu_held, c.grants
        if dev is not None:
            dev_free, dev_sel, granted, rows = _device_accept(
                dev, dev_free, dreq, choice, accept, order_pos, width)
            lost = accept & dreq.wants & ~granted
            accept = accept & ~lost
            grants = grants.replace(
                devices=deviceshare.DeviceGrants(
                    selection=grants.devices.selection | dev_sel,
                    lost_races=grants.devices.lost_races + lost),
                accept_rows=grants.accept_rows + rows)
        if cpus is not None:
            cpu_held, cpu_sel, granted, rows = _cpuset_accept(
                cpus, cpu_held, creq, choice, accept, order_pos, width)
            grants = grants.replace(accept_rows=grants.accept_rows + rows)
            lost = accept & creq.wants & ~granted
            accept = accept & ~lost
            if dev is not None:
                # a pod that lost its cores gives its devices back
                back = lost[:, None] & dev_sel
                dev_free = dev_free.at[jnp.where(lost, choice, 0)].add(
                    back[:, :, None] * dreq.ask[:, None, :])
                grants = grants.replace(devices=grants.devices.replace(
                    selection=grants.devices.selection & ~back))
            # a node takes round by round, and in priority order in one
            grants = grants.replace(cpus=numa.CPUGrants(
                selection=grants.cpus.selection | cpu_sel,
                lost_races=grants.cpus.lost_races + lost,
                turn=jnp.where(granted, i * pods.capacity + order_pos,
                               grants.cpus.turn)))

        safe = jnp.where(accept, choice, 0)
        add = jnp.where(accept[:, None], pods.requests, 0)
        requested = c.requested.at[safe].add(add)
        new_quota = c.quota
        if new_quota is not None:
            new_quota = charge_quota_batch(
                new_quota, pods.requests, pods.quota_id, accept,
                pods.non_preemptible,
            )
        return _RoundCarry(
            requested=requested,
            assignments=jnp.where(accept, choice, c.assignments),
            # free capacity and quota headroom only shrink within a solve,
            # so a pod with no fitting admitted candidate now (act=False)
            # can never gain one: drop it from active so the early-exit
            # condition actually converges
            active=act & ~accept,
            quota=new_quota,
            dev_free=dev_free,
            cpu_held=cpu_held,
            grants=grants,
        )

    # early-exit loop: most rounds converge long before the bound (pods
    # either accept or run out of fitting candidates); the tail rounds are
    # pure waste at the north-star shape
    def cond(loop_carry):
        i, c = loop_carry
        return (i < rounds) & jnp.any(c.active)

    def body(loop_carry):
        i, c = loop_carry
        return i + 1, round_body(i, c)

    _, carry = jax.lax.while_loop(cond, body, (jnp.int32(0), carry))
    new_state = state.replace(node_requested=carry.requested)
    if not with_grants:
        return carry.assignments, new_state, carry.quota
    if dev is not None:
        new_state = new_state.replace(
            devices=dev.replace(free=carry.dev_free))
    if cpus is not None:
        new_state = new_state.replace(cpus=cpus.replace(held=carry.cpu_held))
    return carry.assignments, new_state, carry.quota, carry.grants


# ---------------------------------------------------------------------------
# Incremental delta-driven solve: persistent device-resident candidate cache
# ---------------------------------------------------------------------------
#
# Steady-state scheduler rounds arrive as small deltas (a few node upserts,
# a few pod arrivals) yet the full solve pays O(P·N) candidate selection
# every round.  The cache keeps the (P, k) candidate set resident across
# rounds and refreshes it in O(P·D + Pd·N) for D dirty nodes and Pd dirty
# pods:
#
#   1. pods whose cached candidates touch NO dirty node keep them — their
#      cached top-k over clean nodes IS the clean-column top-k (removing
#      entries ranked below the k-th never changes a top-k), so merging in
#      a fresh top-k over the dirty COLUMNS reproduces the full pass's
#      top-k exactly, per stratum;
#   2. pods that are new/changed, or whose cached candidates touch a dirty
#      node (their clean-column top-k is NOT recoverable from the cache),
#      are fully rescored — the scheduler compacts them into a small batch
#      and scatters the fresh rows over the merge's output.
#
# Exactness holds for the exact top_k methods; under "approx"/"chunked"
# the full pass is itself recall-approximate and the refresh (which always
# merges with exact top_k) is just another recall-approximate candidate
# source.  Either way a stale candidate can only cost RECALL, never
# correctness: acceptance (_assign_rounds) re-checks fit and quota exactly
# every round.


@struct.dataclass
class CandidateCache:
    """Device-resident candidate state carried across scheduler rounds."""

    cand_key: jax.Array    # (P, k) int32 stratum-0 ranking key, -1 invalid
    cand_node: jax.Array   # (P, k) int32 node rows
    cand_score: jax.Array  # (P, k) int32 raw clipped score, -1 invalid

    @classmethod
    def build(cls, cand_key, cand_node, cand_score) -> "CandidateCache":
        return cls(cand_key=cand_key, cand_node=cand_node,
                   cand_score=cand_score)


def align_candidate_cache(
    cache: CandidateCache,
    map_rows: jnp.ndarray,   # (P,) int32 cached row per current batch row
    map_ok: jnp.ndarray,     # (P,) bool — current row present in the cache
    dirty_mask: jnp.ndarray,  # (N,) bool — nodes whose state changed
) -> tuple[CandidateCache, jnp.ndarray]:
    """Gather cached rows into the CURRENT batch's row order and flag pods
    whose cached candidates touch a dirty node.  Keys/scores are functions
    of (rot_id, node, score) only — row-independent — so a gathered row is
    exactly the pod's cached candidate set regardless of queue churn.

    Returns (aligned cache, touch): ``touch[i]`` means row i's cached
    candidates intersect the dirty nodes, so the merge alone cannot
    reproduce its full top-k and the pod must rescore fully."""
    node = cache.cand_node[map_rows]
    score = jnp.where(map_ok[:, None], cache.cand_score[map_rows], -1)
    key = jnp.where(map_ok[:, None], cache.cand_key[map_rows], -1)
    touch = jnp.any(dirty_mask[node] & (score >= 0), axis=1)
    return CandidateCache(key, node, score), touch


@jax.named_scope("refresh")
def refresh_candidates(
    state: ClusterState,
    pods: PodBatch,
    cfg: ScoringConfig,
    cache: CandidateCache,
    dirty_rows: jnp.ndarray,   # (D,) int32, padded; global node rows
    dirty_valid: jnp.ndarray,  # (D,) bool — real (non-pad) entries
    k: int = CAND_K,
    spread_bits=CAND_SPREAD_BITS,
) -> tuple[jnp.ndarray, CandidateCache]:
    """Segmented per-stratum top-k merge of fresh dirty-COLUMN candidates
    into an (aligned) candidate cache.

    Scores only the (P, D) dirty sub-problem, invalidates cached slots
    that point at dirty nodes, recomputes each stratum's ranking keys from
    the cached raw scores, and keeps the best k_i per stratum of
    (cached ∪ fresh-dirty).  For a pod whose cached candidates touch no
    dirty node this equals the full pass's selection exactly (see module
    section comment); rows the scheduler rescores fully are scattered
    over this function's output afterwards.

    Returns (cand_key, new_cache); cand_node rides the cache.
    """
    strata = (tuple(spread_bits) if isinstance(spread_bits, (tuple, list))
              else (spread_bits,))
    n = state.capacity
    k = min(k, n)
    d = dirty_rows.shape[0]
    rot = pods.rot_id

    sub = state.gather_rows(dirty_rows, dirty_valid)
    with jax.named_scope("score"):
        scores, feasible = score_pods(sub, pods, cfg)    # (P, D)
    clipped = jnp.clip(scores, 0, _SCORE_CLIP)
    # .max (OR), not .set: padded dirty_rows entries default to row 0
    # with valid=False, and a duplicate-index .set scatter is
    # order-undefined — it could erase row 0's genuine dirty bit
    dirty_mask = jnp.zeros(n, bool).at[dirty_rows].max(dirty_valid)
    stale_score = jnp.where(dirty_mask[cache.cand_node], -1,
                            cache.cand_score)

    splits = _stratum_splits(k, len(strata))
    nodes_out, scores_out = [], []
    off = 0
    for sb, k_i in zip(strata, splits):
        if k_i == 0:
            continue
        seg_node = cache.cand_node[:, off:off + k_i]
        seg_score = stale_score[:, off:off + k_i]
        off += k_i
        dkey, dtb = _rank_parts(scores, feasible, sb, rot,
                                node_ids=dirty_rows, n_total=n)
        if k_i < d:
            dval, idx = _topk_by_rank(dkey, dtb, k_i, n)
            d_node = dirty_rows[idx]
            d_score = jnp.where(
                dval >= 0, jnp.take_along_axis(clipped, idx, axis=1), -1)
        else:
            dval = dkey
            d_node = jnp.broadcast_to(dirty_rows[None, :], dkey.shape)
            d_score = jnp.where(dval >= 0, clipped, -1)
        c_key = _candidate_keys(seg_score, seg_node, rot, sb, n)
        m_key = jnp.concatenate([c_key, dval], axis=1)
        m_node = jnp.concatenate([seg_node, d_node], axis=1)
        m_score = jnp.concatenate([seg_score, d_score], axis=1)
        mval, midx = _topk_by_rank(
            m_key, _candidate_tb(m_node, rot, n), k_i, n)
        nodes_out.append(jnp.take_along_axis(m_node, midx, axis=1))
        scores_out.append(jnp.where(
            mval >= 0, jnp.take_along_axis(m_score, midx, axis=1), -1))

    cand_node = (jnp.concatenate(nodes_out, axis=1)
                 if len(nodes_out) > 1 else nodes_out[0])
    cand_score = (jnp.concatenate(scores_out, axis=1)
                  if len(scores_out) > 1 else scores_out[0])
    cand_key = _candidate_keys(cand_score, cand_node, rot, strata[0], n)
    return cand_key, CandidateCache(cand_key, cand_node, cand_score)


@jax.named_scope("scatter_rows")
def scatter_candidate_rows(
    cache: CandidateCache,
    rows: jnp.ndarray,        # (S,) int32; out-of-range padding drops
    src_key: jnp.ndarray,     # (S, k)
    src_node: jnp.ndarray,
    src_score: jnp.ndarray,
) -> CandidateCache:
    """Overwrite the fully-rescored (dirty-pod) rows into the cache —
    the compacted select's output scattered back to global batch rows."""
    return CandidateCache(
        cand_key=cache.cand_key.at[rows].set(src_key, mode="drop"),
        cand_node=cache.cand_node.at[rows].set(src_node, mode="drop"),
        cand_score=cache.cand_score.at[rows].set(src_score, mode="drop"),
    )


def assign_round_pass(
    state: ClusterState,
    pods: PodBatch,
    quota: QuotaDeviceState | None,
    cand_key: jnp.ndarray,
    cand_node: jnp.ndarray,
    cfg: ScoringConfig,
    rounds: int = SOLVE_ROUNDS,
    with_grants: bool = False,
):
    """First solve pass over precomputed candidates, with the est-usage
    accumulation and quota recharge :func:`~koordinator_tpu.ops.gang.
    gang_assign` applies between passes — bit-identical to gang_assign's
    first pass over a GANGLESS batch (the incremental scheduler path only
    runs when the round has no gang pods).

    Returns (assignments, new_state, new_quota, est_accum), and the
    device grants after them with ``with_grants``."""
    from koordinator_tpu.ops.assignment import pod_estimates

    a, new_state, _, grants = _assign_rounds(
        state, pods, quota, cand_key, cand_node, rounds, with_grants=True)
    if not with_grants:
        new_state = keep_planes(new_state, state)
    keep = a >= 0
    est = pod_estimates(pods, cfg)
    node = jnp.where(keep, a, 0)
    est_accum = jnp.zeros_like(state.node_usage).at[node].add(
        jnp.where(keep[:, None], est, 0))
    new_quota = quota
    if quota is not None:
        # the in-rounds quota feedback is discarded and recharged whole,
        # exactly as gang_assign does after rollback
        new_quota = charge_quota_batch(
            quota, pods.requests, pods.quota_id, keep, pods.non_preemptible)
    if with_grants:
        return a, new_state, new_quota, est_accum, grants
    return a, new_state, new_quota, est_accum


def assign_followup_pass(
    state: ClusterState,
    est_accum: jnp.ndarray,
    pods: PodBatch,
    quota: QuotaDeviceState | None,
    cfg: ScoringConfig,
    k: int = CAND_K,
    rounds: int = SOLVE_ROUNDS,
    spread_bits=CAND_SPREAD_BITS,
    method: str = "auto",
    with_grants: bool = False,
):
    """A later gang_assign pass over the (compacted) leftover pods:
    candidates re-selected against the est-augmented state, assignments
    committed into the UN-augmented accounting (gang_assign's rollback
    rebuild).  Candidate selection is row-independent and rot_id rides
    the compacted batch, so solving the compacted leftovers equals
    solving the full batch with everyone else masked invalid.

    Returns (assignments, new_state, new_quota, est_accum'), and the
    device grants after them with ``with_grants``."""
    from koordinator_tpu.ops.assignment import pod_estimates

    solve_state = state.replace(
        node_usage=state.node_usage + est_accum,
        node_agg_usage=state.node_agg_usage + est_accum)
    a, _, _, grants = batch_assign(
        solve_state, pods, cfg, quota, k=k, rounds=rounds,
        spread_bits=spread_bits, method=method, with_grants=True)
    keep = (a >= 0) & pods.valid
    node = jnp.where(keep, a, 0)
    add = jnp.where(keep[:, None], pods.requests, 0)
    new_state = state.replace(
        node_requested=state.node_requested.at[node].add(add))
    if with_grants:
        new_state = apply_grants(new_state, pods, node, keep, grants)
    est = pod_estimates(pods, cfg)
    est_accum = est_accum.at[node].add(jnp.where(keep[:, None], est, 0))
    new_quota = quota
    if quota is not None:
        new_quota = charge_quota_batch(
            quota, pods.requests, pods.quota_id, keep, pods.non_preemptible)
    if with_grants:
        return a, new_state, new_quota, est_accum, grants
    return a, new_state, new_quota, est_accum
