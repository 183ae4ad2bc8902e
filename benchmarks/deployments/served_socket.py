"""The scheduler as deployed: ``koord-scheduler`` assembled by
``cmd.binaries.MAINS`` with a listen socket; solve requests (and, for the
traffic kinds that say so, state pushes) are frames on that socket from one
synchronous ``RpcClient`` in this process, as the Go-plugin feeder would
send them.  Bulk arrival goes through the assembly's own
``StateSyncService`` mutators: the consistent path (store, delta log,
binding), without 62k frames of set-up.
"""

from __future__ import annotations

import os
import shutil
import time

import numpy as np

from benchmarks.books import Books
from benchmarks.reference import generators

#: a wire client's timeout must cover the in-line compile of a cold round
COLD_TIMEOUT_S = 1_000.0


def held_by(sched) -> dict:
    """What a scheduler holds, read back from its snapshot."""
    with sched.lock:
        snap = sched.snapshot
        snap.flush()
        state = snap.state
        alloc = np.asarray(state.node_allocatable)
        usage = np.asarray(state.node_usage)
        requested = np.asarray(state.node_requested)
        index = dict(snap.node_index)
        return {
            "alloc": {n: alloc[r] for n, r in index.items()},
            "usage": {n: usage[r] for n, r in index.items()},
            "requested": {n: requested[r] for n, r in index.items()},
            "pending": set(sched.pending),
            "bound": {p: b.node for p, b in sched.bound.items()},
            "bound_requests": {p: np.asarray(b.requests)
                               for p, b in sched.bound.items()},
        }


class Deployment:
    #: solve paths a full round may take / the steady incremental one
    FULL_PATHS = ("full_cold", "full_fallback")

    def __init__(self, config: dict, sizes: dict, seed: int, run_dir: str):
        from koordinator_tpu.cmd.binaries import MAINS

        self.config, self.sizes = config, sizes
        self.dims, self.qos = config["resource_dims"], config["qos"]
        self.rng = np.random.default_rng(seed)
        self.books = Books(self.dims["count"])
        self.serial = 0
        self.standing_now = 0
        self.run_dir = run_dir
        os.makedirs(run_dir, exist_ok=True)
        # a relative path: AF_UNIX paths are capped at ~107 bytes and the
        # checkout may sit anywhere
        sock = os.path.join(run_dir, "sched.sock")
        # ``scheduler_flags``: what the configuration states of the
        # program's own options, beyond the cluster's size
        self.assembled = MAINS["koord-scheduler"]([
            "--disable-leader-election", "--node-capacity",
            str(sizes["nodes"]), "--listen-socket", sock,
            *config.get("scheduler_flags", [])])
        self.scheduler = self.assembled.component
        self.sync = self.assembled.state_sync
        self.sock = sock
        self.client = None

    # -- set-up -------------------------------------------------------------

    def load_nodes(self, replays: int = 1) -> None:
        if replays != 1:
            raise NotImplementedError("one assembled scheduler, one cluster: "
                                      "this deployment cannot replay")
        n = self.sizes["nodes"]
        alloc, usage = generators.make_nodes(self.rng, n, self.dims)
        names = [f"n{i}" for i in range(n)]
        self.books.set_nodes(names, alloc, usage)
        self.hang_up()
        for i, name in enumerate(names):
            self.sync.upsert_node(name, alloc[i], usage=usage[i])

    def standing(self, first: int = 0, last: int | None = None) -> list[tuple]:
        """The pods that fit no node: never offered, always diagnosed."""
        whale = generators.whale_request(self.dims)
        last = self.sizes["standing"] if last is None else last
        return [(f"whale{i}", whale, 9_500, self.qos["LS"], {})
                for i in range(first, last)]

    def set_standing(self, n: int) -> None:
        """Grow or shrink the set of pods that fit no node to ``n``."""
        if n > self.standing_now:
            self.offer(self.standing(self.standing_now, n), counts=False)
        else:
            self.withdraw(self.standing(n, self.standing_now))
        self.standing_now = n

    def wave(self, n: int | None = None) -> list[tuple]:
        """``n`` pods (default: the configuration's wave) as
        (name, request, priority, qos, extra add_pod kwargs)."""
        n = self.sizes["wave_pods"] if n is None else n
        req, prio, qos = generators.make_pods(self.rng, n, self.dims,
                                              self.qos)
        base = self.serial
        self.serial += n
        return [(f"p{base + i}", req[i], int(prio[i]), int(qos[i]), {})
                for i in range(n)]

    def warm_up(self, params: dict, plan: list | None = None) -> None:
        """The standing pods and ``warm_rounds`` rounds of a full wave's pod
        count each, drained on the measured cluster: every program of the
        shape compiles or loads here, also the one that only a round over
        an existing candidate cache runs.  The warm-up pods ask
        ``1 / warm_request_divisor`` of a pod's requests, so that they
        leave the cluster's headroom to the measured waves.

        ``warm_standing`` gives, round by round, how many pods that fit no
        node stand in the queue; after the last round it is the
        configuration's count.  What a round's first pass leaves over is
        solved again in a batch padded to a power of two, and the
        configuration's standing pods fill one exactly: one pod of a wave
        left over beside them takes the next size up.  Which of the two a
        round takes must not hang on the seed, in set-up least of all, so
        the mix puts one round safely inside each."""
        divisor = params.get("warm_request_divisor", 1)
        standing = params.get("warm_standing", [])
        for warm_round in range(params.get("warm_rounds", 1)):
            self.set_standing(standing[warm_round] if warm_round < len(standing)
                              else self.sizes["standing"])
            pods = [(name, request // divisor, *rest)
                    for name, request, *rest in self.wave()]
            self.offer(pods, counts=False)
            t0 = time.perf_counter()
            self.solve()
            if warm_round == 0:
                self.first_round_s = time.perf_counter() - t0
            for _ in range(2):
                if not any(p[0] in self.books.pending for p in pods):
                    break
                self.solve()
        self.set_standing(self.sizes["standing"])

    # -- arrivals -----------------------------------------------------------

    def connected(self):
        """The one wire client, dialled when a frame is next due."""
        if self.client is None:
            from koordinator_tpu.transport import RpcClient

            self.client = RpcClient(self.sock, timeout=COLD_TIMEOUT_S)
            self.client.connect()
        return self.client

    def hang_up(self) -> None:
        if self.client is not None:
            self.client.close()
            self.client = None

    def offer(self, pods: list[tuple], counts: bool = True) -> None:
        """In-process arrival through the sync service's mutator.  No wire
        client is connected meanwhile: the service would broadcast every
        event to it as a DELTA frame, and a burst of 50,000 overruns the
        connection's bounded send queue, which poisons the connection."""
        self.hang_up()
        for name, request, priority, qos, extra in pods:
            self.books.offer(name, request, counts)
            self.sync.add_pod(name, request, priority=priority, qos=qos,
                              **extra)

    def withdraw(self, pods: list[tuple]) -> None:
        """Pending pods leave again, through the same mutators."""
        self.hang_up()
        for name, *_ in pods:
            self.books.withdraw(name)
            self.sync.remove_pod(name)

    def push(self, doc: dict, arrays: dict | None = None) -> None:
        from koordinator_tpu.transport.wire import FrameType

        self.connected().call(FrameType.STATE_PUSH, doc, arrays)

    def push_pod(self, pod: tuple) -> None:
        name, request, priority, qos, _ = pod
        self.books.offer(name, request, stamp=True)
        self.push({"kind": "pod_add", "name": name, "priority": priority,
                   "qos": qos}, {"requests": request})

    # -- rounds -------------------------------------------------------------

    def solve(self) -> int:
        from koordinator_tpu.transport.services import solve_remote

        return self.books.record_round(solve_remote(self.connected()))

    def path_ok(self, path: str, want: str) -> bool:
        """Did a window round take the path the traffic kind names: any
        full path of this deployment, or exactly the one named."""
        return path in self.FULL_PATHS if want == "full" else path == want

    @property
    def round_seq(self) -> int:
        return self.scheduler.round_seq

    def flight_records(self, after_round: int) -> list[dict]:
        return [r.to_doc() for r in list(self.scheduler.flight_recorder.records)
                if r.round > after_round]

    # -- after the window ---------------------------------------------------

    def held(self) -> dict:
        return held_by(self.scheduler)

    def verify(self) -> dict[str, int]:
        return self.books.verify(self.held())

    def close(self) -> None:
        # an in-flight round must finish before teardown, or the
        # interpreter exits under it
        with self.scheduler.lock:
            pass
        self.hang_up()
        self.assembled.stop()
        shutil.rmtree(self.run_dir, ignore_errors=True)
