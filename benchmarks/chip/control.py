"""Readings of the comparison's controls at a cell's own size.

    python3 benchmarks/chip/control.py --workload <cell> --seeds 1 2 3

For each seed, the first ``check.sample`` graphs of the cell's pool in
the seed's order are ordered by each control put in the program's place:
``bfloat16``, the plain reference with its balance sums and gains one
precision below the configuration's float32 (every addition rounded),
and ``separators_first``, the reference with each separator eliminated
before the parts it separates.  Prints one JSON line per seed and
control with the worst reading of each compared number beside its
limit.  The benchmark's own runs do not run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import check  # noqa: E402
import pool as pool_mod  # noqa: E402
import spec  # noqa: E402

CONTROLS = ("bfloat16", "separators_first")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = spec.load_cell(os.path.dirname(os.path.dirname(HERE)),
                          args.workload)
    limits = cell.config["check"]
    k = int(limits["sample"])
    for seed in args.seeds:
        graphs = pool_mod.build_pool(cell.config, seed)[:k]
        for control in CONTROLS:
            worst = {}
            for req in graphs:
                got = check.readings(req.n, req.edges, None, control)
                for name, v in got.items():
                    if name in limits:
                        worst[name] = max(worst.get(name, 0.0), v)
            print(json.dumps({
                "workload": args.workload, "seed": seed,
                "control": control,
                "numbers": {name: [v, limits[name]]
                            for name, v in worst.items()},
                "fails": any(v > limits[name]
                             for name, v in worst.items())}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
