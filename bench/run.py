#!/usr/bin/env python3
"""Run one cell of the provisioning planner's benchmark on the chip.

    python3 bench/run.py --workload paper.grid --seed 7 --seconds 30 --trace 0

Prints, as its last line, one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and with ``--trace
1`` a ``breakdown``) and, last, ``checks``: each number compared with the
reference beside its limit. With ``--trace 0`` the metrics are the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics.
Exits non-zero, printing no result, without a TPU or with fewer chips
than the cell asks for.
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    from bench import harness
    cell = harness.Cell(harness.load_spec(), args.workload)
    try:
        devices = harness.require_chips(cell.chips)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    cache = harness.configure_jax(cell)
    print(f"bench: compile cache {cache}", file=sys.stderr, flush=True)
    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), devices)
    harness.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
