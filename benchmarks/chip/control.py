"""Readings of the comparison's controls at a cell's own size.

    python3 benchmarks/chip/control.py --workload <cell> --seeds 1 2 3
    python3 benchmarks/chip/control.py --config <file> --seeds 1 2 3

``--config`` reads a configuration file that no cell names yet, so its
limits can be set before its cell is added.  For each seed, the first
``check.sample`` graphs of the pool in the seed's order are ordered by
each control put in the program's place: ``bfloat16``, the plain
reference with its balance sums and gains one precision below the
configuration's float32 (every addition rounded), and
``separators_first``, the reference with each separator eliminated
before the parts it separates.  Prints one JSON line per seed and
control with the worst reading of each compared number beside its limit,
and of each other reading as not compared.  The benchmark's own runs do
not run this.
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
    which = ap.add_mutually_exclusive_group(required=True)
    which.add_argument("--workload", help="a cell of BENCHMARK.json")
    which.add_argument("--config", help="a configuration file")
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    if args.config:
        with open(args.config) as f:
            config = json.load(f)
        label = {"config": args.config}
    else:
        config = spec.load_cell(os.path.dirname(os.path.dirname(HERE)),
                                args.workload).config
        label = {"workload": args.workload}
    limits = config["check"]
    k = int(limits["sample"])
    for seed in args.seeds:
        graphs = pool_mod.build_pool(config, seed)[:k]
        for control in CONTROLS:
            worst = dict.fromkeys(check.READINGS, 0.0)
            for req in graphs:
                got = check.readings(req.n, req.edges, None, control)
                for name, v in got.items():
                    worst[name] = max(worst[name], v)
            print(json.dumps({
                **label, "seed": seed, "control": control,
                "numbers": {name: [v, limits[name]]
                            for name, v in worst.items() if name in limits},
                "not_compared": {name: v for name, v in worst.items()
                                 if name not in limits},
                "fails": any(v > limits[name] for name, v in worst.items()
                             if name in limits)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
