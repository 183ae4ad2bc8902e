"""Deferred request accounting: Reserve / Unreserve accumulate on the host
and fold into ``node_requested`` at the next read of ``snapshot.state``.

What the fold is held to:

  (book)     after ANY interleaving of calls, ``node_requested`` at every
             read is bit for bit what a plain numpy book kept beside the
             snapshot holds, and never negative
  (one fold) k deltas then one read is ONE device op; a second read folds
             nothing
  (in flight) a delta taken between a round's dispatch and its collect
             lands in the adopted state
  (mesh)     a node-axis-sharded state stays sharded through a fold and
             equals the single-device result
  (disaster) a conservative rebuild with deltas pending neither raises
             nor leaves a row under its true bookings
  (readers)  a thread without the scheduler's lock (a /debug/slo scrape)
             folds nothing and waits for nobody; a state held across a
             later reserve-then-read stays readable (a preemption pass
             whose ``preempt_fn`` reads the state); a dead row's deltas
             are not counted as folded; ``reserve_batch`` is all or none
"""

import threading
from types import SimpleNamespace

import numpy as np
import pytest

from tests.conftest import prop_seeds
from tests.test_scheduler import mk_scheduler, node, pod

from koordinator_tpu import metrics
from koordinator_tpu.api.resources import NUM_RESOURCE_DIMS
from koordinator_tpu.scheduler.scheduler import BoundPod
from koordinator_tpu.scheduler.snapshot import ClusterSnapshot

R = NUM_RESOURCE_DIMS


def _vec(rng) -> np.ndarray:
    return rng.integers(1, 500, R).astype(np.int32)


def _counters() -> tuple[float, float]:
    return (metrics.snapshot_requested_folds.value(),
            metrics.snapshot_requested_deltas_folded.value())


class _Book:
    """The plain reference: one numpy add per call, applied at once."""

    def __init__(self, snap: ClusterSnapshot):
        self.snap = snap
        self.requested = np.zeros((snap.capacity, R), np.int64)
        #: rows freed and neither flushed nor reused: the device keeps the
        #: dead node's accounting there until one of the two zeroes it
        self.stale: set[int] = set()
        #: live charges as (node, generation, vector)
        self.charges: list[tuple[str, int, np.ndarray]] = []

    def grow_to(self, capacity: int) -> None:
        grown = np.zeros((capacity, R), np.int64)
        grown[: len(self.requested)] = self.requested
        self.requested = grown

    def check(self, where: str) -> None:
        got = np.asarray(self.snap.state.node_requested)
        assert got.dtype == np.int32
        rows = [r for r in range(self.snap.capacity) if r not in self.stale]
        assert (got[rows] == self.requested[rows]).all(), (
            f"{where}: node_requested parted from the book\n"
            f"{got[rows]}\nvs\n{self.requested[rows]}")
        assert (got[rows] >= 0).all(), where


@pytest.mark.parametrize("seed", prop_seeds(12))
def test_any_interleaving_reads_what_the_book_holds(seed):
    rng = np.random.default_rng(seed)
    snap = ClusterSnapshot(capacity=4)
    book = _Book(snap)
    serial = 0
    for step in range(120):
        live = sorted(snap.node_index)
        op = int(rng.integers(0, 12))
        where = f"seed {seed} step {step} op {op}"
        if op <= 1 or not live:
            # a new node: a fresh row, a reused one, or a grown capacity
            name = f"n{serial}" if rng.random() < 0.6 or serial == 0 \
                else f"n{int(rng.integers(0, serial))}"
            serial += 1
            if name not in snap.node_index:
                row = snap.upsert_node(node(name))
                if snap.capacity != len(book.requested):
                    book.grow_to(snap.capacity)
                book.stale.discard(row)
                assert not book.requested[row].any(), where
        elif op == 2:
            name = live[int(rng.integers(0, len(live)))]
            row = snap.node_index[name]
            snap.remove_node(name)
            book.requested[row] = 0
            book.stale.add(row)
        elif op <= 4:
            name = live[int(rng.integers(0, len(live)))]
            vec = _vec(rng)
            snap.reserve(name, vec)
            book.requested[snap.node_index[name]] += vec
            book.charges.append((name, snap.node_generation[name], vec))
        elif op == 5:
            batch = {}
            for name in rng.choice(live, min(len(live), 3), replace=False):
                batch[str(name)] = _vec(rng)
            snap.reserve_batch(batch)
            for name, vec in batch.items():
                book.requested[snap.node_index[name]] += vec
                book.charges.append((name, snap.node_generation[name], vec))
        elif op <= 8 and book.charges:
            # a release, of a live instance's charge or of a dead one's
            name, gen, vec = book.charges.pop(
                int(rng.integers(0, len(book.charges))))
            lives = (name in snap.node_index
                     and snap.node_generation[name] == gen)
            if lives and rng.random() < 0.5:
                snap.unreserve(name, vec)
            else:
                snap.unreserve_instance(name, vec, gen)
            if lives:
                book.requested[snap.node_index[name]] -= vec
        elif op == 9:
            snap.flush()
            book.stale.clear()
        elif op == 10:
            # a solve: dispatched on the state as read, a delta taken
            # while it is in flight, then its result adopted
            state = snap.state
            bound = np.zeros((snap.capacity, R), np.int32)
            rows = [snap.node_index[n] for n in live[:2]]
            for row in rows:
                bound[row] = _vec(rng)
            solved = state.replace(
                node_requested=state.node_requested + bound)
            name = live[int(rng.integers(0, len(live)))]
            vec = _vec(rng)
            snap.reserve(name, vec)
            book.requested[snap.node_index[name]] += vec
            book.charges.append((name, snap.node_generation[name], vec))
            snap.adopt_state(solved, changed_rows=rows)
            book.requested += bound
            for row in rows:
                book.charges.append((snap.node_name(row),
                                     snap.node_generation[snap.node_name(row)],
                                     bound[row]))
        if rng.random() < 0.5:
            book.check(where)
    snap.flush()
    book.stale.clear()
    book.check(f"seed {seed} end")


@pytest.mark.parametrize("k", [1, 7, 500])
def test_k_releases_then_one_read_is_one_fold(k):
    rng = np.random.default_rng(k)
    snap = ClusterSnapshot(capacity=64)
    names = [f"n{i}" for i in range(40)]
    for name in names:
        snap.upsert_node(node(name))
    snap.flush()
    vecs = [(names[int(rng.integers(0, len(names)))], _vec(rng))
            for _ in range(k)]
    for name, vec in vecs:
        snap.reserve(name, vec)
    _ = snap.state
    folds0, deltas0 = _counters()
    for name, vec in vecs:
        snap.unreserve(name, vec)
    assert _counters() == (folds0, deltas0), "a release touched the device"
    first = snap.state
    assert _counters() == (folds0 + 1, deltas0 + k)
    assert not np.asarray(first.node_requested).any()
    assert snap.state is first, "a read with nothing pending made a state"
    assert _counters() == (folds0 + 1, deltas0 + k)


def _bound_book(sched) -> np.ndarray:
    want = np.zeros((sched.snapshot.capacity, R), np.int64)
    for rec in sched.bound.values():
        want[sched.snapshot.node_index[rec.node]] += rec.requests
    return want


@pytest.mark.parametrize("read_between", [False, True],
                         ids=["unread", "read_between"])
@pytest.mark.parametrize("delta", ["release", "reserve", "both"])
def test_delta_between_dispatch_and_collect_lands_in_adopted_state(
        delta, read_between):
    sched, _ = mk_scheduler([node(f"n{i}") for i in range(4)])
    for i in range(6):
        sched.enqueue(pod(f"a{i}", cpu=1_500))
    assert len(sched.schedule_round().assignments) == 6
    for i in range(4):
        sched.enqueue(pod(f"b{i}", cpu=2_000))
    with sched.lock:
        handle = sched.round_device()
        if delta in ("release", "both"):
            sched.remove_bound_pod("a0")
            sched.remove_bound_pod("a3")
        if delta in ("reserve", "both"):
            late = pod("late", cpu=700)
            sched.add_bound_pod(BoundPod(
                name="late", node="n2", requests=late.requests))
        if read_between:
            # folds into the solve's in-flight state, early: the host
            # half must adopt that, not a state from before the fold
            _ = sched.snapshot.state
        result = sched.round_host(handle)
    assert len(result.assignments) == 4
    got = np.asarray(sched.snapshot.state.node_requested)
    assert (got == _bound_book(sched)).all()


@pytest.mark.parametrize("capacity", [64, 256])
def test_fold_keeps_node_axis_sharding_and_the_single_device_sum(capacity):
    import jax

    from koordinator_tpu.scheduler.solver_kit import SolverKit

    kit = SolverKit(shard_min_nodes=0)
    sharding = kit.node_sharding
    sharded, single = ClusterSnapshot(capacity), ClusterSnapshot(capacity)
    sharded.set_state_placement(kit.place)
    assert kit.sharding_active_for(sharded.capacity)
    rng = np.random.default_rng(capacity)
    names = [f"n{i}" for i in range(capacity - 3)]
    for snap in (sharded, single):
        for name in names:
            snap.upsert_node(node(name))
        snap.flush()
    for _ in range(3):
        for _ in range(50):
            name, vec = names[int(rng.integers(0, len(names)))], _vec(rng)
            for snap in (sharded, single):
                snap.reserve(name, vec)
        got = sharded.state.node_requested
        assert got.sharding.is_equivalent_to(sharding, got.ndim)
        assert len({s.device for s in got.addressable_shards}) == len(
            jax.devices())
        assert (np.asarray(got)
                == np.asarray(single.state.node_requested)).all()
    assert np.asarray(got).any()


@pytest.mark.parametrize("pending", ["reserve", "release", "both"])
def test_conservative_rebuild_with_deltas_pending(pending):
    import jax

    sched, _ = mk_scheduler([node(f"n{i}") for i in range(3)])
    for i in range(5):
        sched.enqueue(pod(f"p{i}", cpu=2_000))
    assert len(sched.schedule_round().assignments) == 5
    _ = sched.snapshot.state
    if pending in ("release", "both"):
        sched.remove_bound_pod("p1")
    if pending in ("reserve", "both"):
        late = pod("late", cpu=900)
        sched.add_bound_pod(BoundPod(
            name="late", node="n0", requests=late.requests))
    # the donated-then-failed solve: every buffer of the state is gone,
    # and a fold into them would raise
    for leaf in jax.tree.leaves(sched.snapshot._state):
        leaf.delete()
    with sched.lock:
        sched._recover_solve_failure()
    state = sched.snapshot.state
    got = np.asarray(state.node_requested)
    valid = np.asarray(state.node_valid)
    assert (got[valid] == np.asarray(state.node_allocatable)[valid]).all()
    assert (got >= _bound_book(sched)).all()
    assert not got[~valid].any()
    # a later release of a pod the rebuild covered stays at or over the
    # true bookings
    sched.remove_bound_pod("p2")
    assert (np.asarray(sched.snapshot.state.node_requested)
            >= _bound_book(sched)).all()


def test_debug_slo_scrape_off_the_lock_folds_nothing():
    from koordinator_tpu.scheduler.services import debug_slo_body

    sched, _ = mk_scheduler([node(f"n{i}") for i in range(3)])
    sched.slo_monitor = SimpleNamespace(report=lambda: {"slos": []})
    for i in range(3):
        sched.enqueue(pod(f"p{i}", cpu=2_000))
    assert len(sched.schedule_round().assignments) == 3
    _ = sched.snapshot.state
    body: dict = {}
    with sched.lock:
        # the sync thread's half-done work: a delta taken, not yet read
        sched.remove_bound_pod("p1")
        before = _counters()
        pending = sched.snapshot._pending.copy()
        scrape = threading.Thread(
            target=lambda: body.update(debug_slo_body(sched)))
        scrape.start()
        scrape.join(timeout=30)
        assert not scrape.is_alive(), "the scrape waited for the lock"
        assert body["sharding"]["device_bytes_by_shard"]["cluster_state"]
        assert _counters() == before, "a scrape folded"
        assert (sched.snapshot._pending == pending).all()
    got = np.asarray(sched.snapshot.state.node_requested)
    assert _counters() == (before[0] + 1, before[1] + 1)
    assert (got == _bound_book(sched)).all()


def test_state_held_across_a_fold_stays_readable_and_stale():
    snap = ClusterSnapshot(capacity=8)
    snap.upsert_node(node("n0"))
    snap.flush()
    vec = np.arange(1, R + 1, dtype=np.int32)
    snap.reserve("n0", vec)
    held = snap.state
    snap.reserve("n0", vec)
    fresh = snap.state
    assert fresh is not held
    assert (np.asarray(held.node_requested)[snap.node_index["n0"]]
            == vec).all()
    assert (np.asarray(fresh.node_requested)[snap.node_index["n0"]]
            == 2 * vec).all()


def test_preempt_fn_that_reads_the_state_mid_pass():
    sched, _ = mk_scheduler([node("n1", cpu=4_000), node("n2", cpu=4_000)],
                            enable_preemption=True)
    for i in range(4):
        sched.enqueue(pod(f"low{i}", cpu=2_000, priority=10 + i))
    assert len(sched.schedule_round().assignments) == 4
    seen = []

    def evict(victim, preemptor):
        # the victim's release is pending here: this read folds it, under
        # the preemption pass's own evolving state
        seen.append(np.asarray(sched.snapshot.state.node_requested).copy())

    sched.preempt_fn = evict
    sched.enqueue(pod("high0", cpu=2_000, priority=9_500))
    sched.enqueue(pod("high1", cpu=2_000, priority=9_400))
    res = sched.schedule_round()
    assert set(res.nominations) == {"high0", "high1"}
    assert len(seen) == 2 and all((got >= 0).all() for got in seen)
    assert set(sched.schedule_round().assignments) == {"high0", "high1"}
    assert (np.asarray(sched.snapshot.state.node_requested)
            == _bound_book(sched)).all()


@pytest.mark.parametrize("others", [0, 1], ids=["alone", "beside_a_live_row"])
def test_a_dead_rows_deltas_are_not_counted_as_folded(others):
    snap = ClusterSnapshot(capacity=8)
    for name in ("n0", "n1"):
        snap.upsert_node(node(name))
    snap.flush()
    _ = snap.state
    before = _counters()
    vec = np.ones(R, np.int32)
    snap.reserve("n0", vec)
    snap.reserve("n0", vec)
    for _ in range(others):
        snap.reserve("n1", vec)
    snap.remove_node("n0")
    got = np.asarray(snap.state.node_requested)
    assert _counters() == (before[0] + (1 if others else 0),
                           before[1] + others)
    assert not got[0].any()
    assert (got[snap.node_index["n1"]] == others * vec).all()


def test_reserve_batch_with_an_unknown_node_accounts_nothing():
    snap = ClusterSnapshot(capacity=8)
    snap.upsert_node(node("n0"))
    snap.flush()
    _ = snap.state
    before = _counters()
    vec = np.ones(R, np.int32)
    with pytest.raises(KeyError):
        snap.reserve_batch({"n0": vec, "gone": vec})
    assert not np.asarray(snap.state.node_requested).any()
    assert _counters() == before
