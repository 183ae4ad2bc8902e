"""Which program a SolverKit entry runs, read off what the kit counts.

Every twinned stage has ONE entry on the kit; the entry picks the
single-device program or the ``shard_map`` program from its own
arguments.  Nothing above the kit can tell which ran except by the
``shape`` label that ``solver_recompiles_total`` gained, so that label
(and the private binding whose jit cache grew) is what these cases
assert, stage by stage, on each side of every term of the choice.
"""

import numpy as np
import pytest

from koordinator_tpu import metrics
from koordinator_tpu.ops import batch_assign as ba
from koordinator_tpu.ops.assignment import ScoringConfig
from koordinator_tpu.ops.gang import GangInfo
from koordinator_tpu.scheduler.solver_kit import SolverKit
from koordinator_tpu.state.cluster_state import PodBatch

from tests.problem_helpers import build_problem

#: 8 shards divide N and N_FLOOR, not N_ODD.  Sizes no other test
#: uses, and one capacity a scenario: jits of one function share one
#: compile cache across kits, and a label is gained only on a miss
N, N_FLOOR, N_ODD, P = 72, 136, 68, 32

#: stage -> (recompile label ``fn``, private binding stem)
STAGES = {
    "solve": ("gang_assign", "_solve"),
    "forecast_solve": ("forecast_gang_assign", "_forecast_solve"),
    "quality_solve": ("lp_pack_assign", "_quality_solve"),
    "select_scored": ("select_candidates", "_select_scored"),
    "refresh_cands": ("refresh_candidates", "_refresh_cands"),
    "pass1": ("assign_round_pass", "_pass1"),
    "pass2": ("assign_followup_pass", "_pass2"),
}

#: scenario -> (kit, node capacity, factored batch, the capacity solves
#: on the mesh)
SCENARIOS = {
    "no_mesh": ("off", N, True, False),
    "mesh_floor_0": ("mesh", N, True, True),
    "under_floor": ("floor", N_FLOOR, True, False),
    "nodes_axis_does_not_divide": ("mesh", N_ODD, True, False),
    # a hinted batch: only the gang/greedy twin needs the factored mask
    "dense_mask": ("mesh", N, False, True),
}
CASES = [(stage, scenario) for scenario in SCENARIOS for stage in STAGES
         if scenario != "dense_mask"
         or stage in ("solve", "forecast_solve")]


@pytest.fixture(scope="module")
def kits():
    made = {"off": SolverKit(mesh="off"),
            "mesh": SolverKit(mesh="auto", shard_min_nodes=0),
            "floor": SolverKit(mesh="auto")}
    assert made["mesh"].shards == 8 and made["floor"].shards == 8
    for kit in made.values():
        kit.rounds = 1     # one unrolled round a pass: compile time
    return made


def _recompiles():
    return {(lbl.get("fn"), lbl.get("shape")): v
            for lbl, v in metrics.solver_recompiles.items()}


def _problem(n, factored):
    state, pods = build_problem(n_nodes=n, n_pods=P, seed=7)
    if not factored:
        # a hinted round's batch: the dense (P, N) feasibility mask
        pods = PodBatch.build(
            np.asarray(pods.requests), priority=np.asarray(pods.priority),
            node_capacity=n, capacity=pods.capacity,
            feasible=np.ones((P, n), bool))
        assert pods.selector_mask is None
    return state, pods


def _run(kit, stage, n, factored):
    """Call one entry on a fresh seeded problem; (outputs, label gained,
    bindings whose jit cache grew)."""
    import jax.numpy as jnp

    cfg = ScoringConfig.default()
    state, pods = _problem(n, factored)
    gangs = GangInfo.build(np.zeros(0, np.int32))
    args = {
        "solve": lambda: (state, pods, cfg, gangs, None),
        "forecast_solve": lambda: (
            state, jnp.zeros_like(state.node_requested), pods, cfg,
            gangs, None),
        "quality_solve": lambda: (state, pods, cfg, None),
        "select_scored": lambda: (state, pods, cfg),
        "pass1": lambda: (state, pods, None) + tuple(
            ba.select_candidates(state, pods, cfg, method="exact")) + (cfg,),
        "pass2": lambda: (state, jnp.zeros_like(state.node_requested),
                          pods, None, cfg),
        "refresh_cands": lambda: (
            state, pods, cfg,
            ba.CandidateCache.build(*ba.select_candidates(
                state, pods, cfg, k=min(ba.CAND_K, n), method="exact",
                with_scores=True)),
            jnp.arange(64, dtype=jnp.int32) % n, jnp.arange(64) < 5),
    }[stage]()
    kw = ({"passes": 1, "solver": "greedy"}
          if stage in ("solve", "forecast_solve") else {})
    stem = STAGES[stage][1]
    one, sh = getattr(kit, stem + "_one"), getattr(kit, stem + "_sh")
    before = _recompiles()
    misses = (one.misses, sh.misses if sh else 0)
    out = getattr(kit, stage)(*args, **kw)
    gained = {k for k, v in _recompiles().items() if v > before.get(k, 0)}
    grew = {"one": one.misses > misses[0],
            "sh": bool(sh) and sh.misses > misses[1]}
    return out, gained, grew


@pytest.mark.parametrize("stage,scenario", CASES)
def test_the_kit_picks_the_program_from_its_arguments(kits, stage,
                                                      scenario):
    which, n, factored, active = SCENARIOS[scenario]
    sharded = active and factored
    kit = kits[which]
    out, gained, grew = _run(kit, stage, n, factored)
    assert grew == {"one": not sharded, "sh": sharded}, (gained, grew)
    shape = f"P{P}xN{n}" + ("xD64" if stage == "refresh_cands" else "")
    # the label says the capacity solves on the mesh, the binding which
    # program ran: the GSPMD-placed single-device solve of a dense batch
    # keeps the suffix.  The single-device refresh and follow-up
    # programs never label the mesh (no suffix term in their shape_of)
    if active and (sharded or stage not in ("refresh_cands", "pass2")):
        shape += "@8shard"
    assert gained == {(STAGES[stage][0], shape)}
    if factored:
        assert kit.selection(n, _problem(n, True)[1]) == (
            "sharded" if active else kit.method)
    if scenario == "mesh_floor_0":
        # the placements the repo already holds bit-identical on the
        # CPU ("auto" is the exact selection here): same pods, same
        # accounting as the single-device program on the same problem
        ref, _, _ = _run(kits["off"], stage, n, factored)
        for got, want in zip(out[:2], ref[:2]):
            if hasattr(want, "node_requested"):
                got, want = got.node_requested, want.node_requested
            elif hasattr(want, "cand_key"):
                got, want = got.cand_key, want.cand_key
            np.testing.assert_array_equal(np.asarray(got),
                                          np.asarray(want))


def test_place_follows_the_same_predicate(kits):
    """``place`` shards a state exactly where the entries run sharded:
    identity with no mesh, under the floor and where the nodes axis does
    not divide the capacity."""
    for which, n, want in (("off", N, 1), ("floor", N_FLOOR, 1),
                           ("mesh", N_ODD, 1), ("mesh", N, 8)):
        kit = kits[which]
        state, _ = build_problem(n_nodes=n, n_pods=P)
        placed = kit.place(state)
        assert kit.sharding_active_for(n) == (want == 8)
        assert len({s.device.id for s in
                    placed.node_requested.addressable_shards}) == want
        if want == 1:
            assert placed is state
