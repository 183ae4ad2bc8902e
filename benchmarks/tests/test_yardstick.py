"""The yardstick's own arithmetic: bytes from shapes, names and units."""

from __future__ import annotations

import json
import os
import re

import pytest

from benchmarks import solve_bytes

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_least_bytes_is_a_pure_function_of_shapes_and_rises():
    base = solve_bytes.least_bytes(65_536, 10_240, 10, 1)
    assert base == solve_bytes.least_bytes(65_536, 10_240, 10, 1)
    assert solve_bytes.least_bytes(131_072, 10_240, 10, 1) > base
    assert solve_bytes.least_bytes(65_536, 20_480, 10, 1) > base
    assert solve_bytes.least_bytes(65_536, 10_240, 10, 2) > base
    # by hand: reads 65,536 x 12 + 10,240 x 30 int32, writes 65,536 +
    # 10,240 x 10 int32
    assert base == 4 * (65_536 * 12 + 10_240 * 30 + 65_536 + 102_400)
    with pytest.raises(ValueError):
        solve_bytes.least_bytes(0, 10_240, 10, 1)


@pytest.mark.parametrize("pods,bucket", [(1, 1), (2_348, 4_096),
                                         (52_048, 65_536), (65_536, 65_536)])
def test_pad_pow2(pods, bucket):
    assert solve_bytes.pad_pow2(pods) == bucket


def test_benchmark_json_names_units_and_files():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    metrics = bench["end_to_end"] + bench["per_layer"]
    names = ([m["name"] for m in metrics]
             + [w["name"] for w in bench["workloads"]]
             + [c["name"] for c in bench["configs"]]
             + [w["traffic"] for w in bench["workloads"]])
    for name in names:
        assert NAME.match(name), name
    for m in metrics:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    here = os.path.join(ROOT, "benchmarks")
    for m in bench["end_to_end"]:
        assert os.path.exists(os.path.join(here, "end_to_end",
                                           m["name"] + ".py"))
    for m in bench["per_layer"]:
        assert os.path.exists(os.path.join(here, "layers", m["name"] + ".py"))
        assert m["moves"] in {e["name"] for e in bench["end_to_end"]}
    for w in bench["workloads"]:
        assert os.path.exists(os.path.join(here, "traffic",
                                           w["traffic"] + ".json"))
        assert len(w["why"]) <= 200


def test_peaks_lookup_errors_on_unknown_device():
    with open(os.path.join(ROOT, "benchmarks", "peaks.json")) as f:
        peaks = json.load(f)
    assert peaks["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9
    assert peaks.get("TPU v9 imaginary") is None
