"""chip_smoke.py and the bench scripts hold themselves to the chip: no TPU,
no result; a failed phase, a non-zero exit.  What tier-1 can exercise on
the CPU: the refusal, the explicit tiny dry-run (1 and 4 virtual devices),
the plain-reference checkers on bad input, and the compile-cache helper."""

import ast
import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")
BENCHES = ("bench.py", "bench_micro.py", "bench_recall.py",
           "bench_stages.py")
SCRIPTS = ("chip_smoke.py", *BENCHES)
#: bench.py with its TPU gate stepped over (this is the CPU) and its first
#: timed phase made to raise
BENCH_PHASE_RAISES = (
    "import bench\n"
    "bench.require_tpu = lambda allow_cpu=False: {'platform': 'x'}\n"
    "bench.N_NODES, bench.N_PODS = 64, 128\n"
    "def boom(*a, **k):\n"
    "    raise RuntimeError('phase failed')\n"
    "bench._median_readback_seconds = boom\n"
    "bench.main()\n")


def _cpu_env(n_devices: int) -> dict:
    return dict(os.environ, JAX_PLATFORMS="cpu",
                XLA_FLAGS=f"--xla_force_host_platform_device_count="
                          f"{n_devices}")


@pytest.fixture(scope="module")
def runs():
    """Every subprocess run of this module, started together (they are
    independent, and each is mostly start-up and compilation):
    label -> (returncode, stdout, stderr)."""
    commands = {
        "no_flag": ([SMOKE], 1),
        "dry1": ([SMOKE, "--cpu-dry-run"], 1),
        "dry4": ([SMOKE, "--cpu-dry-run"], 4),
        "phase_raises": (["-c", BENCH_PHASE_RAISES], 1),
        **{script: ([os.path.join(REPO, script)], 1) for script in BENCHES},
    }
    procs = {
        label: subprocess.Popen(
            [sys.executable, *argv], cwd=REPO, env=_cpu_env(n_devices),
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
        for label, (argv, n_devices) in commands.items()}
    out = {}
    try:
        for label, proc in procs.items():
            stdout, stderr = proc.communicate(timeout=600)
            out[label] = (proc.returncode, stdout, stderr)
    finally:
        for proc in procs.values():
            if proc.poll() is None:
                proc.kill()
    return out


def test_refuses_to_run_without_a_tpu(runs):
    rc, stdout, stderr = runs["no_flag"]
    assert rc != 0
    assert "'cpu'" in stderr and "not 'tpu'" in stderr
    # it says what it found first, and prints no result
    first = stdout.splitlines()[0]
    assert first.startswith("DEVICE ") and '"platform": "cpu"' in first
    assert '"ok"' not in stdout


@pytest.mark.parametrize("n_devices", [1, 4])
def test_cpu_dry_run_passes(runs, n_devices):
    rc, stdout, stderr = runs[f"dry{n_devices}"]
    assert rc == 0, stderr[-3000:]
    lines = stdout.splitlines()
    last = json.loads(lines[-1])
    assert last == {"ok": True, "dry_run": True,
                    "device": {"platform": "cpu", "kind": "cpu",
                               "count": n_devices}}
    readouts = json.loads(next(
        line for line in lines if line.startswith("SMOKE_READOUTS ")
    ).split(" ", 1)[1])
    assert readouts["dry_run"] is True and readouts["claim"] is None
    # the mesh is engaged on 4 devices, and only there
    assert readouts["mesh"] == {"shards": n_devices,
                                "devices_holding_nodes": n_devices}
    rounds = [json.loads(line.split(" ", 1)[1]) for line in lines
              if line.startswith("ROUND ")]
    by_label = {r["label"]: r for r in rounds}
    assert by_label["drain0"]["path"] == "full_cold"
    assert by_label["gang_quota"]["path"] == "full_gang"
    warm = [r for r in rounds if r["label"].startswith("steady")][-3:]
    assert [r["path"] for r in warm] == ["incremental"] * 3
    assert len({r["recompiles_total"] for r in warm}) == 1


class TestPlainReference:
    """The checkers must fail on placements that break the guarantees —
    a checker that cannot fail proves nothing on the chip."""

    @pytest.fixture
    def smoke(self):
        sys.path.insert(0, REPO)
        import chip_smoke

        return chip_smoke

    def test_overcommitted_assignment_is_rejected(self, smoke):
        alloc = np.array([[4_000, 8_192], [4_000, 8_192]], np.int32)
        requests = {f"p{i}": np.array([1_500, 1_024], np.int32)
                    for i in range(3)}
        names = ["n0", "n1"]
        ok = {"p0": "n0", "p1": "n0", "p2": "n1"}
        assert smoke.check_no_overcommit(alloc, names, requests, ok) == 3
        # 3 x 1,500 mcpu on a 4,000 mcpu node
        with pytest.raises(smoke.SmokeError, match="over allocatable"):
            smoke.check_no_overcommit(
                alloc, names, requests, {p: "n0" for p in requests})
        with pytest.raises(smoke.SmokeError, match="unknown nodes"):
            smoke.check_no_overcommit(alloc, names, requests, {"p0": "nX"})

    def test_partial_gang_is_rejected(self, smoke):
        members = {"g0": ["a", "b", "c"], "g1": ["d", "e", "f"]}
        whole = {"a": "n", "b": "n", "c": "n"}
        assert smoke.check_gangs(members, 3, whole) == (1, 1)
        with pytest.raises(smoke.SmokeError, match="partially bound"):
            smoke.check_gangs(members, 3, dict(whole, d="n"))

    def test_quota_overrun_is_rejected(self, smoke):
        requests = {p: np.array([600, 0], np.int64) for p in "abc"}
        pod_quota = {p: "q" for p in "abc"}
        limits = {"q": np.array([1_500, -1], np.int64)}   # dim 1 unbounded
        smoke.check_quotas(pod_quota, requests, {"a": "n", "b": "n"},
                           limits, "max")
        with pytest.raises(smoke.SmokeError, match="over its max"):
            smoke.check_quotas(pod_quota, requests,
                               {p: "n" for p in "abc"}, limits, "max")


class TestCompileCache:
    def test_env_var_places_the_cache(self, monkeypatch, tmp_path):
        from koordinator_tpu import compile_cache

        monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
        assert compile_cache.enable_compile_cache() == str(tmp_path)

    def test_default_is_the_fixed_in_checkout_path(self, monkeypatch):
        from koordinator_tpu import compile_cache

        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
        assert compile_cache.DEFAULT_DIR == os.path.join(REPO, ".jax_cache")
        assert compile_cache.enable_compile_cache() == os.path.join(
            REPO, ".jax_cache")
        with open(os.path.join(REPO, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()

    def test_written_where_placed_and_hit_by_the_next_process(
            self, tmp_path):
        """Two processes, one placed directory: the first writes there
        (and JAX's configured directory is that one, no other), the
        second reports a hit."""
        child = (
            "import json, jax, jax.numpy as jnp\n"
            "from koordinator_tpu.compile_cache import (cache_events,\n"
            "    enable_compile_cache)\n"
            "path = enable_compile_cache(); events = cache_events()\n"
            "jax.jit(lambda x: (x @ x.T).sum())(jnp.ones((64, 64)))"
            ".block_until_ready()\n"
            "print(json.dumps({'path': path, 'jax_dir': "
            "jax.config.jax_compilation_cache_dir, **events}))\n")
        env = dict(_cpu_env(1), JAX_COMPILATION_CACHE_DIR=str(tmp_path),
                   JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
                   JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
        runs = []
        for _ in range(2):
            proc = subprocess.run([sys.executable, "-c", child], cwd=REPO,
                                  env=env, capture_output=True, text=True,
                                  timeout=300)
            assert proc.returncode == 0, proc.stderr[-2000:]
            runs.append(json.loads(proc.stdout.strip().splitlines()[-1]))
        for run in runs:
            assert run["path"] == run["jax_dir"] == str(tmp_path)
        assert runs[0]["misses"] >= 1 and os.listdir(tmp_path)
        assert runs[1]["hits"] >= 1


class TestBenchScriptsFailLoud:
    def test_bench_exits_nonzero_when_a_phase_raises(self, runs):
        """bench.py has no path from a raising phase to exit 0 or to a
        printed record."""
        rc, stdout, stderr = runs["phase_raises"]
        assert rc != 0
        assert "phase failed" in stderr
        assert stdout.strip() == ""

    @pytest.mark.parametrize("script", BENCHES)
    def test_bench_refuses_the_cpu_unless_asked(self, runs, script):
        rc, stdout, stderr = runs[script]
        assert rc != 0
        assert "not 'tpu'" in stderr
        assert stdout.strip() == ""

    @pytest.mark.parametrize("script", SCRIPTS)
    def test_no_path_from_a_failure_to_a_field_or_exit_0(self, script):
        """Structural: no broad handler that could turn a failed phase
        into a record field, no hard exit, and no child Python process
        (one process holds the chip) — git for the provenance stamp is
        the only thing these scripts may spawn."""
        with open(os.path.join(REPO, script)) as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            if isinstance(node, ast.ExceptHandler):
                caught = ast.unparse(node.type) if node.type else "bare"
                assert caught not in ("bare", "Exception", "BaseException"), (
                    f"{script}:{node.lineno} catches {caught}")
            if isinstance(node, ast.Call):
                called = ast.unparse(node.func)
                assert called != "os._exit", f"{script}:{node.lineno}"
                if called.startswith("subprocess."):
                    assert '"git"' in ast.unparse(node) or \
                        "'git'" in ast.unparse(node), (
                        f"{script}:{node.lineno} spawns a process")
