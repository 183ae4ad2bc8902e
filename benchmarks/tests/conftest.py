"""Four virtual CPU devices for the benchmark's own tests, so that the cells
of a four-chip host rehearse their mesh here.  Must run before JAX starts."""

import os

FLAG = "--xla_force_host_platform_device_count"
if FLAG not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + f" {FLAG}=4").strip()
