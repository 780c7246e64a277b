#!/usr/bin/env python3
"""Readings that set the limits of ``compare.LIMITS``: a cell run on
several seeds in one process, at the precision its configuration states
(the lower readings) or one step below it (the control, bfloat16 packs).

    python3 bench/control.py --workload paper.grid --precision bfloat16 \\
        --seconds 10 --seeds 1 2 3

Prints one JSON line per seed with every number compared. The
benchmark's own runs (``bench/run.py``) never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--precision", choices=("float32", "bfloat16"),
                    default="float32")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    from bench import harness
    cell = harness.Cell(harness.load_spec(), args.workload)
    try:
        devices = harness.require_chips(cell.chips)
    except harness.NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    cell.config["precision"] = args.precision
    harness.configure_jax(cell)
    for seed in args.seeds:
        res = harness.run_cell(cell, seed, args.seconds, False, devices)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "precision": args.precision,
                          "correct": res["correct"],
                          "attempted": res["attempted"],
                          "failed": res["failed"],
                          "metrics": res["metrics"],
                          "numbers": res["numbers"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
