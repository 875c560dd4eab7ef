#!/usr/bin/env python3
"""python3 perfbench/limits.py --workload <cell> --seeds 11,12,... --seconds <s> [--control-seeds 3]

Reads, on the chip and at the cell's own size and load, the two numbers a
limit of ``correct`` is set from: the largest that sound runs of the
program give over the seeds, and the smallest that the lower-precision
control gives. One process: each seed is a whole short run (new weights,
new fleet, a short window at the cell's load, the comparison), the first
``--control-seeds`` of them with the control read as well. Not part of a
benchmark run."""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    import argparse

    from perfbench import core

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--control-seeds", type=int, default=3)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    rows = []
    for i, seed in enumerate(seeds):
        res = core.run_cell(args.workload, seed, args.seconds, 0,
                            t_start=time.monotonic(),
                            control=i < args.control_seeds)
        v = res["check"]
        rows.append({"seed": seed, "correct": res["correct"],
                     "attempted": res["attempted"], "failed": res["failed"],
                     "tokens": v["tokens"], "compared": v.get("compared"),
                     "control": v.get("control"),
                     "metrics": {k: m["value"]
                                 for k, m in res["metrics"].items()}})
        print(json.dumps({"phase": "seed", **rows[-1]}), flush=True)
    keys = sorted(rows[0]["compared"])
    summary = {"phase": "limits"}
    for k in keys:
        sound = [r["compared"][k] for r in rows if r["compared"]]
        ctrl = [r["control"][k] for r in rows if r["control"]]
        summary[k] = {"sound_max": max(sound), "sound_min": min(sound),
                      "control_min": min(ctrl) if ctrl else None,
                      "control_max": max(ctrl) if ctrl else None}
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
