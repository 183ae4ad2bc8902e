"""Run ``gpushare_cycle`` with one planted fault in the program.

    python3 benchmarks/tests/gpu_fault_run.py --fault <name> --workload gpushare_cycle --seed <n> --seconds <s> --trace 0

``--fault`` is a key of ``test_gpushare_cell.FAULTS``; the other arguments
are ``benchmarks/run.py``'s (add ``--cpu-dry-run`` off the chip).  The fault
is planted the way the test plants it (``fault_run.Plant``), so the run at
the cell's own size on the chip breaks what the rehearsal breaks.  The last
line must read ``"correct": false``, with the fault's own number among the
failing.
"""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(argv: list[str]) -> int:
    from benchmarks import run
    from benchmarks.tests.fault_run import Plant
    from benchmarks.tests.test_gpushare_cell import FAULTS

    if len(argv) < 2 or argv[0] != "--fault" or argv[1] not in FAULTS:
        raise SystemExit(f"usage: gpu_fault_run.py --fault "
                         f"{{{','.join(FAULTS)}}} <run.py's arguments>")
    plant, number = FAULTS[argv[1]]
    plant(Plant)
    print(f"FAULT {argv[1]}: expect {number} above 0", flush=True)
    return run.main(argv[2:])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
