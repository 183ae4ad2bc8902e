"""The device half's toolbox: every jitted solver entry, and the ONE
place that decides where and how a solve runs.

A :class:`SolverKit` holds the compiled programs of the batch solver
(gang/greedy solve, candidate selection / refresh / scatter, the
propose/accept passes, the LP packing solve, the forecast-charged
solve, the reservation pre-pass, preemption, explain / slack
reductions) and is shareable: the tenants of a ``TenantScheduler``
multiplex onto one kit, so T clusters share one jit cache, one
recompile ledger and one mesh.

Three decisions live here and nowhere above:

- **placement**: each twinned stage exists as a single-device program
  and as an explicit ``shard_map`` program over the solve mesh
  (``parallel/sharded.py``).  A caller calls ONE entry per stage
  (``solve``, ``forecast_solve``, ``quality_solve``, ``select_scored``,
  ``refresh_cands``, ``pass1``, ``pass2``); the entry picks its program
  from its own arguments through :meth:`SolverKit._sharded`.  Which
  program ran shows only in the recompile label's ``@Nshard`` /
  ``@PxNshard`` suffix.  :meth:`place` / :meth:`place_batch` put the
  state and the cached batch where those programs read them in place.
- **the candidate method**: ``"auto"`` resolved once, through
  ``ops/batch_assign.resolve_candidate_method`` (the rule's one home).
- **the candidate parameters**: ``ops/batch_assign``'s ``CAND_K``,
  ``CAND_SPREAD_BITS``, ``SOLVE_ROUNDS``.

A fourth follows from the first: **where the device stage runs**.  Over
a state that carries devices (``ClusterState.devices``) the single-
device programs run DeviceShare's Filter and Reserve inside the solve,
and ``solve``, ``forecast_solve``, ``pass1`` and ``pass2`` hand the
grants back as their last value.  The ``shard_map`` twins and the LP
packing solve know no device: ``_sharded`` keeps a state with devices
off the twins (GSPMD places the single-device program instead), and
``quality_solve`` returns no grants, so its binds take theirs at the
commit (``Scheduler._grant_devices``).  Without devices every entry
returns ``None`` there and traces the program it always traced.

The mesh itself is a deployment setting (``KOORD_SOLVER_MESH``,
``KOORD_SOLVER_MESH_PODS``: ``parallel/mesh.resolve_solver_mesh``).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from koordinator_tpu.ops import batch_assign as _ba
from koordinator_tpu.parallel import mesh as pmesh


def _no_grants(solved):
    """A gang/greedy twin's (assignments, state, quota, stats) in the
    single-device entry's form: the twins carry no device stage, so the
    grants before the stats are None."""
    assignments, state, quota, stats = solved
    return assignments, state, quota, None, stats


class SolverKit:
    """Construction is cheap (wrapping, not compiling); compilation
    happens per (entry, shape bucket) on first use and is shared by
    every scheduler holding the kit.

    ``mesh``: a ``Mesh``, ``"auto"`` (every visible device on the nodes
    axis when there is more than one) or ``None`` / ``"off"``.
    ``shard_min_nodes``: capacities under it stay single-device (sharding
    a 64-node problem is pure collective overhead).
    """

    def __init__(self, mesh="auto", shard_min_nodes: int = 1024):
        from koordinator_tpu.ops import explain as _ex
        from koordinator_tpu.ops import introspection as insp
        from koordinator_tpu.ops.gang import gang_assign
        from koordinator_tpu.ops.preemption import preempt_chain, preempt_one
        from koordinator_tpu.ops.reservation import reservation_greedy_assign
        from koordinator_tpu.parallel import sharded as psharded
        from koordinator_tpu.quality.lp_pack import lp_pack_assign
        from koordinator_tpu.quality.topo_gang import gang_topo_diameter

        self.mesh = pmesh.resolve_solver_mesh(mesh)
        self.shard_min_nodes = int(shard_min_nodes)
        self.shards = pmesh.nodes_shard_count(self.mesh)
        self.pod_shards = pmesh.pods_shard_count(self.mesh)
        self.node_sharding = (pmesh.node_sharding(self.mesh)
                              if self.mesh is not None else None)
        #: candidate method of the single-device programs (the sharded
        #: selection is recall-exact and takes none)
        self.method = _ba.resolve_candidate_method("auto")
        #: propose/accept rounds of the incremental passes
        self.rounds = _ba.SOLVE_ROUNDS

        def _sfx(state) -> str:
            # a state with devices never meets a twin (``_sharded``)
            if (state.devices is not None
                    or not self.sharding_active_for(state.capacity)):
                return ""
            # the pods=1 form stays "@Nshard": dashboards key on it
            if self.pod_shards > 1:
                return f"@{self.pod_shards}x{self.shards}shard"
            return f"@{self.shards}shard"

        def _pn(args, kwargs):
            return (f"P{args[1].capacity}xN{args[0].capacity}"
                    f"{_sfx(args[0])}")

        # Every jitted entry point is wrapped for recompile accounting
        # (ops/introspection): a cache miss lands in
        # solver_recompiles_total{fn, shape}.  The twins of one stage
        # share the ``fn`` label; the shape's suffix tells them apart.
        # Solve-state donation: the caller's snapshot.state is dead the
        # moment the call starts (XLA updates the (N, R) accounting in
        # place, under its NamedSharding placement on the mesh) and must
        # be replaced wholesale by the returned state.  Without a mesh
        # a twin is False: ``_sharded`` never picks it.
        sh = self.mesh is not None

        # the gang/greedy solve.  The single-device program is GSPMD-
        # placed when handed a sharded state: the fallback for dense-
        # feasibility (hinted) batches, which cannot tile over the mesh
        self._solve_one = insp.instrument(
            jax.jit(gang_assign,
                    static_argnames=("passes", "solver", "with_grants"),
                    donate_argnums=(0,)),
            "gang_assign", shape_of=_pn)
        self._solve_sh = sh and insp.instrument(
            jax.jit(partial(psharded.sharded_gang_assign, self.mesh),
                    static_argnames=("passes", "solver", "k",
                                     "rounds", "spread_bits"),
                    donate_argnums=(0,)),
            "gang_assign", shape_of=_pn)

        # the incremental stages: selection recall-exact on the mesh,
        # acceptance bit-identical (parallel/sharded.py)
        self._select_scored_one = insp.instrument(
            jax.jit(_ba.select_candidates,
                    static_argnames=("k", "spread_bits", "method",
                                     "with_scores")),
            "select_candidates", shape_of=_pn)
        self._select_scored_sh = sh and insp.instrument(
            jax.jit(partial(psharded.sharded_select_candidates, self.mesh),
                    static_argnames=("k", "spread_bits", "with_scores")),
            "select_candidates", shape_of=_pn)
        self.align_cands = insp.instrument(
            jax.jit(_ba.align_candidate_cache),
            "align_candidate_cache",
            shape_of=lambda a, k: (f"P{a[1].shape[0]}xN{a[3].shape[0]}"))
        self._refresh_cands_one = insp.instrument(
            jax.jit(_ba.refresh_candidates,
                    static_argnames=("k", "spread_bits"),
                    donate_argnums=(3,)),
            "refresh_candidates",
            shape_of=lambda a, k: (f"P{a[1].capacity}xN{a[0].capacity}"
                                   f"xD{a[4].shape[0]}"))
        self._refresh_cands_sh = sh and insp.instrument(
            jax.jit(partial(psharded.sharded_refresh_candidates, self.mesh),
                    static_argnames=("k", "spread_bits"),
                    donate_argnums=(3,)),
            "refresh_candidates",
            shape_of=lambda a, k: (
                f"P{a[1].capacity}xN{a[0].capacity}"
                f"xD{a[4].shape[0]}{_sfx(a[0])}"))
        self.scatter_cands = insp.instrument(
            jax.jit(_ba.scatter_candidate_rows, donate_argnums=(0,)),
            "scatter_candidate_rows",
            shape_of=lambda a, k: (f"P{a[0].cand_key.shape[0]}"
                                   f"xS{a[1].shape[0]}"))
        self._pass1_one = insp.instrument(
            jax.jit(_ba.assign_round_pass,
                    static_argnames=("rounds", "with_grants"),
                    donate_argnums=(0,)),
            "assign_round_pass", shape_of=_pn)
        self._pass1_sh = sh and insp.instrument(
            jax.jit(partial(psharded.sharded_assign_round_pass, self.mesh),
                    static_argnames=("rounds",),
                    donate_argnums=(0,)),
            "assign_round_pass", shape_of=_pn)
        self._pass2_one = insp.instrument(
            jax.jit(_ba.assign_followup_pass,
                    static_argnames=("k", "rounds", "spread_bits",
                                     "method", "with_grants"),
                    donate_argnums=(0, 1)),
            "assign_followup_pass",
            shape_of=lambda a, k: f"P{a[2].capacity}xN{a[0].capacity}")
        self._pass2_sh = sh and insp.instrument(
            jax.jit(partial(psharded.sharded_assign_followup_pass,
                            self.mesh),
                    static_argnames=("k", "rounds", "spread_bits"),
                    donate_argnums=(0, 1)),
            "assign_followup_pass",
            shape_of=lambda a, k: (
                f"P{a[2].capacity}"
                f"xN{a[0].capacity}{_sfx(a[0])}"))

        # quality mode: the LP-relaxation packing solve, the second
        # solver backend.  Same donation contract as the greedy entries.
        self._quality_solve_one = insp.instrument(
            jax.jit(lp_pack_assign,
                    static_argnames=("ascent_iters", "rounding_iters"),
                    donate_argnums=(0,)),
            "lp_pack_assign", shape_of=_pn)
        self._quality_solve_sh = sh and insp.instrument(
            jax.jit(partial(psharded.sharded_lp_pack_assign, self.mesh),
                    static_argnames=("ascent_iters", "rounding_iters"),
                    donate_argnums=(0,)),
            "lp_pack_assign", shape_of=_pn)
        #: topology diameter of a placed slot set (quality/topo_gang) —
        #: the rank-aware gang observable bench_recall and the quality
        #: planner report
        self.topo_diameter = jax.jit(gang_topo_diameter)

        # forecast plane: predictive admission — the gang/greedy solve
        # with the forecast-headroom reserve charged for the round
        # (charge -> solve -> release inside ONE jitted program;
        # forecast/kernels).  Donation mirrors gang_assign; the (N, R)
        # reserve at arg1 stays live for the host half's rescue pass.
        from koordinator_tpu.forecast.kernels import forecast_gang_assign

        def _fpn(args, kwargs):
            return (f"P{args[2].capacity}xN{args[0].capacity}"
                    f"{_sfx(args[0])}")

        self._forecast_solve_one = insp.instrument(
            jax.jit(forecast_gang_assign,
                    static_argnames=("passes", "solver", "with_grants"),
                    donate_argnums=(0,)),
            "forecast_gang_assign", shape_of=_fpn)
        self._forecast_solve_sh = sh and insp.instrument(
            jax.jit(partial(psharded.sharded_forecast_gang_assign,
                            self.mesh),
                    static_argnames=("passes", "solver", "k",
                                     "rounds", "spread_bits"),
                    donate_argnums=(0,)),
            "forecast_gang_assign", shape_of=_fpn)

        self.rsv_solve = insp.instrument(
            jax.jit(reservation_greedy_assign, donate_argnums=(0,)),
            "reservation_greedy_assign", shape_of=_pn)

        self.preempt = jax.jit(
            preempt_one, static_argnames=("same_quota_only", "nominate"))
        self.preempt_chain = jax.jit(preempt_chain)

        #: device-side reject-reason reduction over a round's COMPACTED
        #: failed rows — O(F·NUM_REASONS) host transfer, never (P, N)
        self.explain_counts = insp.instrument(
            jax.jit(_ex.explain_counts), "explain_counts", shape_of=_pn)
        #: per-dim capacity-slack reduction ((N, R) -> two (R,) sums);
        #: float32 accumulation — a 10k-node cluster's summed int32
        #: quantities overflow int32, and a ratio gauge doesn't need
        #: integer exactness
        self.slack_sums = insp.instrument(
            jax.jit(lambda st: (
                jnp.sum(jnp.where(
                    st.node_valid[:, None],
                    st.node_allocatable - st.node_requested, 0
                ).astype(jnp.float32), axis=0),
                jnp.sum(jnp.where(
                    st.node_valid[:, None], st.node_allocatable, 0
                ).astype(jnp.float32), axis=0))),
            "capacity_slack",
            shape_of=lambda a, k: f"N{a[0].capacity}")

    # -- where a solve runs ---------------------------------------------------

    def sharding_active_for(self, n_cap: int) -> bool:
        """Do solves over a state of THIS node capacity run on the mesh?
        Derived from the capacity, not from any one scheduler, so a
        shared kit answers for each tenant."""
        return (self.mesh is not None
                and n_cap % self.shards == 0
                and n_cap >= self.shard_min_nodes)

    def _sharded(self, n_cap: int, batch=None, factored=False,
                 devices=False) -> bool:
        """The placement choice, made here and nowhere else: does a
        stage over (a state of ``n_cap`` rows, ``batch``) run its
        ``shard_map`` program?  Never over a state with ``devices``:
        the twins carry no device stage.  Else the stages differ in
        what the program needs besides an active mesh:

        - the LP packing twin replicates pods: nothing (``batch`` None);
        - the incremental stages split pods over the pods axis: a batch
          capacity the axis divides (power-of-two buckets always do for
          power-of-two axes; an odd env-forced axis falls back);
        - the gang/greedy twin besides needs the ``factored`` selector
          mask: a dense (P, N) feasibility mask cannot tile over the
          2-D mesh.
        """
        return (self.sharding_active_for(n_cap)
                and not devices
                and (batch is None
                     or batch.capacity % self.pod_shards == 0)
                and (not factored or batch.selector_mask is not None))

    def place(self, state):
        """Put a cluster state where its solves read and donate it in
        place: node-axis-sharded over the mesh when its capacity solves
        there, untouched otherwise.  The snapshot applies it to every
        state it builds (``ClusterSnapshot.set_state_placement``)."""
        if not self.sharding_active_for(state.capacity):
            return state
        return pmesh.shard_cluster_state(state, self.mesh)

    def place_batch(self, batch, n_cap: int, devices: bool = False):
        """Pin a batch that is reused across rounds under the 2-D
        mesh's pod-axis sharding, so the sharded entries consume it in
        place instead of resharding it per call.  Only where the
        gang/greedy twin would take it (``devices``: the state it meets
        carries some): a single-device entry must not receive a mesh-
        committed batch.  No entry donates the batch."""
        if self.pod_shards > 1 and self._sharded(n_cap, batch, True,
                                                 devices):
            return pmesh.shard_pod_batch(batch, self.mesh)
        return batch

    def selection(self, n_cap: int, batch, devices: bool = False) -> str:
        """Which candidate selection :meth:`select_scored` runs for
        these shapes: ``"sharded"`` or the single-device method's name.
        A candidate cache is valid only for the selection that built
        it."""
        return ("sharded" if self._sharded(n_cap, batch, devices=devices)
                else self.method)

    # -- one entry per stage --------------------------------------------------
    # Each hands its arguments to ONE of its two programs; ``state`` (and
    # pass 2's ``est_accum``, the refresh's ``cache``) is donated either
    # way.  The shape annotations are specflow seed contracts
    # (tools/koordlint): ``state`` is ONE tenant's (N, R) tensors — a
    # tenant-stacked (T, N, R) tensor reaching an entry is a finding.

    # koordlint: shape[state: NxR i32 nodes]
    def solve(self, state, batch, config, gangs, quota, *, passes, solver):
        """``ops/gang.gang_assign``: (assignments, state, quota,
        grants, stats); ``stats`` is the exact scans' ``ScanStats``,
        None from the batch engine."""
        if self._sharded(state.capacity, batch, True,
                         state.devices is not None):
            return _no_grants(self._solve_sh(
                state, batch, config, gangs, quota,
                passes=passes, solver=solver))
        return self._solve_one(state, batch, config, gangs, quota,
                               passes=passes, solver=solver,
                               with_grants=True)

    # koordlint: shape[state: NxR i32 nodes, reserve: NxR i32 nodes]
    def forecast_solve(self, state, reserve, batch, config, gangs, quota,
                       *, passes, solver):
        """``forecast/kernels.forecast_gang_assign``: :meth:`solve` with
        ``reserve`` charged for the duration of the solve."""
        if self._sharded(state.capacity, batch, True,
                         state.devices is not None):
            return _no_grants(self._forecast_solve_sh(
                state, reserve, batch, config, gangs, quota,
                passes=passes, solver=solver))
        return self._forecast_solve_one(
            state, reserve, batch, config, gangs, quota,
            passes=passes, solver=solver, with_grants=True)

    # koordlint: shape[state: NxR i32 nodes]
    def quality_solve(self, state, batch, config, quota):
        """``quality/lp_pack.lp_pack_assign``: (assignments, state,
        quota, iterations).  No device stage: see the module's note."""
        if self._sharded(state.capacity,
                         devices=state.devices is not None):
            return self._quality_solve_sh(state, batch, config, quota)
        return self._quality_solve_one(state, batch, config, quota)

    def select_scored(self, state, batch, config):
        """Full candidate selection: (cand_key, cand_node, cand_score)."""
        k = min(_ba.CAND_K, state.capacity)
        if self._sharded(state.capacity, batch,
                         devices=state.devices is not None):
            return self._select_scored_sh(
                state, batch, config, k=k,
                spread_bits=_ba.CAND_SPREAD_BITS, with_scores=True)
        return self._select_scored_one(
            state, batch, config, k=k, spread_bits=_ba.CAND_SPREAD_BITS,
            method=self.method, with_scores=True)

    def refresh_cands(self, state, batch, config, cache, dirty_rows,
                      dirty_valid):
        """Re-score an aligned candidate cache against the dirty node
        rows: (cand_key, cache)."""
        k = min(_ba.CAND_K, state.capacity)
        if self._sharded(state.capacity, batch,
                         devices=state.devices is not None):
            return self._refresh_cands_sh(
                state, batch, config, cache, dirty_rows, dirty_valid,
                k=k, spread_bits=_ba.CAND_SPREAD_BITS)
        return self._refresh_cands_one(
            state, batch, config, cache, dirty_rows, dirty_valid,
            k=k, spread_bits=_ba.CAND_SPREAD_BITS)

    # koordlint: shape[state: NxR i32 nodes]
    def pass1(self, state, batch, quota, cand_key, cand_node, config):
        """First propose/accept pass over given candidates:
        (assignments, state, quota, est_accum, grants)."""
        if self._sharded(state.capacity, batch,
                         devices=state.devices is not None):
            return (*self._pass1_sh(state, batch, quota, cand_key,
                                    cand_node, config, rounds=self.rounds),
                    None)
        return self._pass1_one(state, batch, quota, cand_key, cand_node,
                               config, rounds=self.rounds, with_grants=True)

    # koordlint: shape[state: NxR i32 nodes, est_accum: NxR i32 nodes]
    def pass2(self, state, est_accum, batch, quota, config):
        """A later pass: full selection over a compacted leftover batch
        against the est-usage-augmented state, then accept:
        (assignments, state, quota, est_accum, grants)."""
        k = min(_ba.CAND_K, state.capacity)
        if self._sharded(state.capacity, batch,
                         devices=state.devices is not None):
            return (*self._pass2_sh(
                state, est_accum, batch, quota, config, k=k,
                rounds=self.rounds, spread_bits=_ba.CAND_SPREAD_BITS),
                None)
        return self._pass2_one(
            state, est_accum, batch, quota, config, k=k,
            rounds=self.rounds, spread_bits=_ba.CAND_SPREAD_BITS,
            method=self.method, with_grants=True)
