"""Test env: force the CPU platform with 8 virtual devices before JAX
initializes.  Multi-chip sharding logic is tested on this virtual mesh; the
chip itself is reached only by ``chip_smoke.py`` through the chip tool.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

# Build the native shim once up front so collector tests exercise the C path
# (lazy loading would otherwise race the background build).
from koordinator_tpu import native as _native  # noqa: E402

_native.ensure_built()

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _isolate_metrics():
    """Zero every metric registry after each test (values only — the
    module-level instrument handles stay registered), so counters stop
    bleeding across tests within one pytest process.  Tests that want
    deltas mid-test still see them; tests that assert absolute values
    start from a clean slate."""
    yield
    from koordinator_tpu import metrics, timeline

    metrics.reset_all_for_tests()
    # the timeline recorder is process-wide like the registries: drop
    # recorded segments/cycles so one test's rounds can't attribute
    # into another's window
    timeline.RECORDER.reset_for_tests()


def prop_seeds(default_n: int) -> list[int]:
    """Seed list for the randomized property suites.

    CI runs the fixed ``range(default_n)``; the soak harness
    (tools/soak.sh) sweeps FRESH seeds by setting
    ``KOORD_PROP_SEED_BASE`` (window start) and ``KOORD_PROP_SEED_COUNT``
    (window size, 0 = each suite's default count).  Every suite keeps its
    own default so CI cost stays where it was tuned, while one env knob
    re-aims all of them at an arbitrary seed window."""
    base = int(os.environ.get("KOORD_PROP_SEED_BASE", "0"))
    count = int(os.environ.get("KOORD_PROP_SEED_COUNT", "0") or 0)
    return list(range(base, base + (count or default_n)))
