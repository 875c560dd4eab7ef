#!/usr/bin/env python3
"""python3 perfbench/limits.py --workload <cell> --seeds 11,12,... --seconds <s> [--control-seeds 3] [--all-finished]

Reads, on the chip and at the cell's own size and load, the two numbers a
limit of ``correct`` is set from: the largest that sound runs of the
program give over the seeds, and the smallest that the lower-precision
control gives. One process: each seed is a whole short run (new weights,
new fleet, a short window at the cell's load, the comparison), the first
``--control-seeds`` of them with the control read as well. Not part of a
benchmark run.

``--all-finished`` hands the reference EVERY request the window finished,
not the cell's sample, and prints one ``request`` row each (``standing``:
in flight when the window opened, or arrived in it; prompt and served
tokens; that request's own ``gap_max``, ``gap_mean``, ``top1_share``), then
the seed's largest single-request readings by population. A limit stands
above the largest value any SINGLE request reads: a sample can be that
request and little else.

    python3 perfbench/limits.py --workload <cell> --draws <file of those rows> [--limits '{"gap_max": 0.1}']

needs no chip: for each seed in the file it walks every sample
``core.pick_sample`` can draw from those requests, for any shuffle, under
the cell's ``"sample"`` and with no place held for an arrival, and prints
the worst pooled numbers and how many samples read over a limit."""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

NUMBERS = ("gap_max", "gap_mean")


def every_sample(sizes, standing, requests: int, token_budget: int,
                 arrived: int = 0):
    """Every list of indices ``core.pick_sample`` can return for SOME
    shuffle of the requests after the longest (``sizes``: prompt + served
    tokens, in the order of ``finished``). Whatever fits the budget can
    come first in a shuffle, so each place is any candidate that fits."""
    if not sizes or requests < 1:
        yield []
        return
    longest = max(range(len(sizes)), key=lambda i: sizes[i])  # first of equals

    def grow(picked, budget, held):
        """``held``: places still kept for arrivals."""
        if len(picked) < requests:
            free = [i for i in range(len(sizes))
                    if i not in picked and sizes[i] <= budget]
            fresh = [i for i in free if not standing[i]]
            pool, left = (fresh, held - 1) if held and fresh else (free, 0)
            if pool:
                for i in pool:
                    yield from grow(picked + [i], budget - sizes[i], left)
                return
        yield picked

    yield from grow([longest], token_budget - sizes[longest], arrived)


def pooled(rows) -> dict:
    """What ``check.summarise`` gives over the positions of ``rows``
    together, from each request's own numbers."""
    tokens = sum(r["tokens"] for r in rows)
    return {"gap_max": max(r["gap_max"] for r in rows),
            "gap_mean": sum(r["gap_mean"] * r["tokens"] for r in rows) / tokens}


def worst_draws(rows, limits: dict, sample: dict) -> dict:
    """Over every sample the rule can draw from one seed's ``rows``: the
    worst pooled reading of each number, and the samples over a limit.
    Where the rows carry the control's readings, its LEAST pooled reading
    over the same samples and the samples on which it would pass: a limit
    is held against what a run can draw, and one request of 16 served
    tokens reads next to nothing for the control on its own."""
    sizes = [r["prompt_tokens"] + r["tokens"] for r in rows]
    standing = [r["standing"] for r in rows]
    control = ([{"tokens": r["tokens"], **r["control"]} for r in rows]
               if all("control" in r for r in rows) else None)
    out = {"samples": 0, "over_a_limit": 0, **{k: 0.0 for k in NUMBERS}}
    if control:
        out.update(control_passes=0,
                   control_least={k: float("inf") for k in NUMBERS})
    for picked in every_sample(sizes, standing, **sample):
        if not picked:
            continue
        got = pooled([rows[i] for i in picked])
        out["samples"] += 1
        out["over_a_limit"] += any(got[k] > limits[k] for k in limits)
        for k in NUMBERS:
            out[k] = max(out[k], got[k])
        if control:
            low = pooled([control[i] for i in picked])
            out["control_passes"] += all(low[k] <= limits[k] for k in limits)
            for k in NUMBERS:
                out["control_least"][k] = min(out["control_least"][k], low[k])
    return out


def read_rows(path: str) -> dict:
    """The ``request`` rows of a saved ``--all-finished`` output, by seed,
    in the order they were printed (the order of ``finished``)."""
    by_seed = {}
    with open(path) as f:
        for line in f:
            if line.startswith("{"):
                row = json.loads(line)
                if row.get("phase") == "request":
                    by_seed.setdefault(row["seed"], []).append(row)
    return by_seed


def draws(spec: dict, path: str, limits: dict) -> int:
    from perfbench import check, families

    dtype = families.load_family(spec["config"]).sizes(spec["config"]).dtype
    limits = {**check.DEFAULT_LIMITS.get(dtype, {}), **spec["limits"],
              **limits}
    for seed, rows in read_rows(path).items():
        for sample in ({**spec["sample"], "arrived": 0}, spec["sample"]):
            print(json.dumps({
                "phase": "draws", "seed": seed, "requests_read": len(rows),
                "arrived_read": sum(not r["standing"] for r in rows),
                "sample": sample, "limits": limits,
                **worst_draws(rows, limits, sample)}), flush=True)
    return 0


def by_population(rows) -> dict:
    """The largest single-request reading of each number, and the pooled
    mean, for the standing requests and for the arrivals; the control's
    smallest single-request reading beside them."""
    out = {}
    for name, want in (("standing", True), ("arrived", False)):
        mine = [r for r in rows if r["standing"] is want]
        out[name] = {"requests": len(mine)}
        if mine:
            out[name].update(
                tokens=sum(r["tokens"] for r in mine),
                pooled_gap_mean=pooled(mine)["gap_mean"],
                **{k: max(r[k] for r in mine) for k in NUMBERS},
                top1_share_min=min(r["top1_share"] for r in mine))
            ctrl = [r["control"] for r in mine if "control" in r]
            if ctrl:
                out[name]["control_min"] = {
                    k: min(c[k] for c in ctrl) for k in NUMBERS}
    return out


def main(argv=None, *, root=None, allow_cpu: bool = False) -> int:
    import argparse

    from perfbench import core

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--all-finished", action="store_true")
    ap.add_argument("--draws", metavar="FILE")
    ap.add_argument("--limits", type=json.loads, default={})
    args = ap.parse_args(argv)
    root = root or core.ROOT
    if args.draws:
        return draws(core.load_cell(args.workload, root), args.draws,
                     args.limits)
    if not args.seeds:
        ap.error("give --seeds (a chip reading) or --draws (a saved one)")
    seeds = [int(s) for s in args.seeds.split(",")]
    rows, requests = [], []
    for i, seed in enumerate(seeds):
        res = core.run_cell(args.workload, seed, args.seconds, 0,
                            t_start=time.monotonic(), root=root,
                            allow_cpu=allow_cpu,
                            control=i < args.control_seeds,
                            all_finished=args.all_finished)
        v = res["check"]
        rows.append({"seed": seed, "correct": res["correct"],
                     "attempted": res["attempted"], "failed": res["failed"],
                     "tokens": v["tokens"], "compared": v.get("compared"),
                     "control": v.get("control"),
                     "metrics": {k: m["value"]
                                 for k, m in res["metrics"].items()}})
        if args.all_finished:
            for r in v.get("per_request", ()):
                print(json.dumps({"phase": "request", "seed": seed, **r}),
                      flush=True)
            rows[-1]["populations"] = by_population(v.get("per_request", ()))
            requests += v.get("per_request", ())
        print(json.dumps({"phase": "seed", **rows[-1]}), flush=True)
    keys = sorted(rows[0]["compared"])
    summary = {"phase": "limits"}
    for k in keys:
        sound = [r["compared"][k] for r in rows if r["compared"]]
        ctrl = [r["control"][k] for r in rows if r["control"]]
        summary[k] = {"sound_max": max(sound), "sound_min": min(sound),
                      "control_min": min(ctrl) if ctrl else None,
                      "control_max": max(ctrl) if ctrl else None}
    if args.all_finished:
        summary["single_request"] = by_population(requests)   # all seeds'
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
