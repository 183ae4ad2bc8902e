"""One run of one benchmark cell.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Finds ``workloads[name]`` in ``BENCHMARK.json`` and then, by the names it
gives: the configuration's file, ``traffic/<traffic>.json``, the traffic
kind that file names (``kinds/<kind>.py``), the configuration's deployment
(``deployments/<deployment>.py``) and one reader per metric the cell reports
(``end_to_end/<metric>.py``, ``layers/<metric>.py``).  Nothing here knows a
cell, a configuration or a metric by name.

Fails unless JAX reports a TPU with exactly the chips the cell asks for.
``--cpu-dry-run`` rehearses the cell at the configuration's ``dry`` sizes on
whatever backend is there; it says so on every line and never prints a
chip's result line.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmarks")
#: sockets live here, addressed relative to ROOT (AF_UNIX caps a path at
#: ~107 bytes and the checkout may sit anywhere)
RUN_DIR = ".bench_run"


def load_json(*parts: str) -> dict:
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def find_cell(bench: dict, workload: str) -> tuple[dict, dict]:
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json "
                         f"has {sorted(cells)}")
    cell = cells[workload]
    config = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    return cell, config


def reported(metrics: list[dict], workload: str) -> list[dict]:
    """The metrics this cell reports: those that list it, or list none."""
    return [m for m in metrics if workload in m.get("workloads", [workload])]


def check_enums(config: dict) -> None:
    """The reference keeps its own copy of the program's dimension order and
    QoS codes; a program that renumbers them must fail here, loudly."""
    from koordinator_tpu.api.qos import QoSClass
    from koordinator_tpu.api.resources import NUM_RESOURCE_DIMS, ResourceDim

    dims = config["resource_dims"]
    theirs = {"count": NUM_RESOURCE_DIMS,
              **{k: int(ResourceDim[k.upper()]) for k in dims if k != "count"}}
    codes = {k: int(QoSClass[k]) for k in config["qos"]}
    if theirs != dims or codes != config["qos"]:
        raise SystemExit(f"configuration's resource dims / QoS codes "
                         f"{dims} {config['qos']} are not the program's "
                         f"{theirs} {codes}")


def recompiles() -> dict[str, int]:
    """``solver_recompiles_total`` by its (fn, shape) labels."""
    from koordinator_tpu import metrics

    return {json.dumps(labels, sort_keys=True): int(v)
            for labels, v in metrics.solver_recompiles.items()}


def timeline_segments(offset: float) -> list[tuple[str, float, float]]:
    """The scheduler's own timeline segments (host ``perf_counter``), moved
    onto the trace's clock."""
    from koordinator_tpu import timeline

    return [(seg["name"] or seg["cause"],
             doc["start"] + seg["start"] + offset,
             doc["start"] + seg["end"] + offset)
            for doc in timeline.RECORDER.cycles(64)
            for seg in doc["segments"]]


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--cpu-dry-run", action="store_true")
    parser.add_argument("--dump-trace", metavar="PATH",
                        help="with --trace 1: also write the reduced trace, "
                             "in trace_reduce's plain form, to PATH")
    args = parser.parse_args(argv)
    dry, traced = args.cpu_dry_run, bool(args.trace)
    tag = "DRY_RUN " if dry else ""

    def say(line: str, **fields) -> None:
        print(f"{tag}{line} {json.dumps(fields, sort_keys=True)}", flush=True)

    os.chdir(ROOT)
    sys.path.insert(0, ROOT)
    bench = load_json("BENCHMARK.json")
    cell, config_entry = find_cell(bench, args.workload)
    config = load_json(config_entry["file"])
    params = load_json("benchmarks", "traffic", f"{cell['traffic']}.json")
    sizes = dict(config["sizes"])
    if traced:
        # a trace holds every device op: where a mix says so, the traced
        # run drives less of the same work (per-layer metrics are means)
        params.update(params.get("traced", {}))
    if dry:
        sizes.update(config["dry"])
        params.update(params.get("dry", {}))

    from koordinator_tpu.compile_cache import (
        cache_events,
        enable_compile_cache,
    )

    cache_dir = enable_compile_cache()
    cache = cache_events()
    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    say("DEVICE", **device, cell_chips=cell["chips"])
    if not dry and (device["platform"] != "tpu"
                    or device["count"] != cell["chips"]):
        raise SystemExit(
            f"benchmarks/run.py: cell {cell['name']!r} needs a TPU with "
            f"{cell['chips']} chip(s), JAX reports {device}; refusing to "
            f"run (--cpu-dry-run is the tiny rehearsal)")
    peak = None if dry else load_json("benchmarks", "peaks.json").get(
        device["kind"])
    if not dry and peak is None:
        raise SystemExit(f"benchmarks/peaks.json has no device kind "
                         f"{device['kind']!r}")
    check_enums(config)

    from benchmarks.context import Context, load_trace
    from benchmarks.spans import Spans

    kind = importlib.import_module(f"benchmarks.kinds.{params['kind']}")
    deployment = importlib.import_module(
        f"benchmarks.deployments.{config['deployment']}")
    spans = Spans(traced)
    trace_dir = tempfile.mkdtemp(prefix="koord-bench-trace-") if traced else None
    dep = deployment.Deployment(config, sizes, args.seed,
                                os.path.join(RUN_DIR, str(os.getpid())))
    try:
        state = kind.setup(dep, params, spans)
        # set-up's garbage goes now, and what set-up built is not walked
        # again by every full collection inside the window
        gc.collect()
        gc.freeze()
        recompiles_before, round_before = recompiles(), dep.round_seq
        if traced:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=options)
        dep.books.window_open = True
        with spans.span("window"):
            t_open = time.perf_counter()
            stats = kind.window(dep, params, state, t_open + args.seconds,
                                spans)
        if traced:
            t_stop = time.perf_counter()
            jax.profiler.stop_trace()
            say("TRACE_COST", stop_trace_s=time.perf_counter() - t_stop)
        memory_peak = max(int((d.memory_stats() or {}).get(
            "peak_bytes_in_use", 0)) for d in devices)
        rounds = dep.flight_records(round_before)
        recompiled = {k: v - recompiles_before.get(k, 0)
                      for k, v in recompiles().items()
                      if v != recompiles_before.get(k, 0)}
        ctx = Context(
            books=dep.books, spans=spans, rounds=rounds, t_start=T_START,
            t_open=t_open, t_close=stats["t_close"],
            recompiles=sum(recompiled.values()),
            first_round_s=dep.first_round_s, peak=peak,
            shapes={"nodes": sizes["nodes"],
                    "dims": config["resource_dims"]["count"],
                    "passes": config["solve_passes"]})
        with spans.span("check"):
            compared = dep.verify()
    finally:
        dep.close()

    paths = [r["solve_path"] if r["solver"] == "batch" else r["solver"]
             for r in rounds]
    compared["wrong_path_rounds"] = sum(
        not dep.path_ok(p, kind.ROUND_PATHS) for p in paths)
    compared["recompiles_in_window"] = ctx.recompiles
    compared["rounds_short"] = max(0, 1 - len(rounds))
    correct = all(v == 0 for v in compared.values())

    breakdown = None
    if traced and dry:
        # no TPU plane to read off the chip: the readers that need the
        # trace find nothing, the others are rehearsed
        shutil.rmtree(trace_dir, ignore_errors=True)
        say("TRACE_NOT_READ", note="dry run")
    elif traced:
        t_load = time.perf_counter()
        try:
            ctx.trace = load_trace(trace_dir, args.dump_trace)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        window_span = spans.named("window")[0]
        breakdown = ctx.trace.breakdown(
            timeline_segments(ctx.trace.window[0] - window_span[1]))
        say("TRACE_COST", read_trace_s=time.perf_counter() - t_load)
        device["busy_s"] = ctx.trace.mean_busy_s
        device["window_s"] = ctx.trace.window_s

    metrics = {}
    which, where = (("per_layer", "layers") if traced
                    else ("end_to_end", "end_to_end"))
    for metric in reported(bench[which], cell["name"]):
        reader = importlib.import_module(
            f"benchmarks.{where}.{metric['name']}")
        value = reader.read(ctx)
        if value is not None:
            metrics[metric["name"]] = {"value": value,
                                       "unit": metric["unit"]}

    books = dep.books
    attempted = len(books.offered)
    failed = books.undiagnosed + books.stray_binds
    say("CACHE", dir=cache_dir, **cache)
    say("COUNTS", rounds=len(rounds), pods_offered=attempted,
        pods_bound=books.bound_in_window,
        pods_in_tail=sum(1 for p in books.offered if p in books.sent_at),
        window_s=ctx.t_close - ctx.t_open,
        **{k: v for k, v in stats.items() if k != "t_close"})
    if stats.get("waves_done", 0) < stats.get("waves_asked", 0):
        say("SHORT", note=f"--seconds ran out: finished "
            f"{stats['waves_done']} of {stats['waves_asked']} waves")
    for r in rounds:
        say("ROUND", round=r["round"], path=r["solve_path"], pods=r["pods"],
            placed=r["placed"], failed=r["failed"],
            duration_s=r["duration_s"], blocked_s=r["solve_device_s"],
            phases={k: round(v, 4) for k, v in r["phase_s"].items()})
    say("PATHS", want=kind.ROUND_PATHS, took=paths)
    if recompiled:
        say("RECOMPILED", programs=recompiled)
    for name, value in compared.items():
        print(f"{tag}COMPARED {name} value={value} limit=0 "
              f"{'ok' if value == 0 else 'FAIL'}", file=sys.stderr)
    sys.stderr.flush()
    device["memory_peak_bytes"] = memory_peak
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = {k: {"value": v, "limit": 0}
                          for k, v in compared.items()}
    print(tag + json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
