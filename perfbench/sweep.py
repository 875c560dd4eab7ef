#!/usr/bin/env python3
"""python3 perfbench/sweep.py --workload <cell> --seed <n> --seconds <s> --rates 0.8,1.0,...

Finds the knee of an open-loop cell once, when the cell is defined: ONE
set-up, then a ladder of ascending arrival rates served one after another
in the same process, each for ``--seconds`` (several times a request's
life). The first leg opens on its rate's standing population; every later
leg takes over what the one before left in flight. For each leg it prints
the requests in flight at each quarter of the leg (a backlog that grows all
through the leg means the rate is above the knee), the program's queue wait,
failures, the waits for a first token and the mean gap between a request's
tokens (the pace a cell's ``standing`` block states). The knee is the highest
rate whose backlog stops growing and at which nothing waits for a slot; the
cell then fixes ``rate_rps`` at about four fifths of it in
``cells/<workload>.json``. Not part of a benchmark run."""

import os
import sys
import time

T_START = time.monotonic()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    import argparse

    from perfbench import core, readers
    from perfbench.traffic_kinds import load_kind

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rates", required=True,
                    help="comma-separated requests per second, ascending")
    args = ap.parse_args(argv)
    spec = core.load_cell(args.workload)
    served, family, sizes, devices, dev, phases, compiles = core.set_up(
        spec, args.seed, t_start=T_START)
    carried = None
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        traffic = {**spec["traffic"], "rate_rps": rate}
        plan = load_kind(traffic["kind"])(
            traffic, seed=args.seed + i, seconds=args.seconds,
            vocab=sizes.vocab_size, max_total=sizes.max_length,
            n_slots=served.n_slots)
        if carried is None:
            carried = core.open_standing(served, plan)
        served.queue_wait_new()
        out = core.drive(served, plan, args.seconds, 0.0, standing=carried)
        rec = core.records_of(out, served, family, sizes, len(devices),
                              dev["kind"])
        def in_flight(share):
            t = rec.t_open + share * args.seconds
            return sum(1 for tr in rec.tracked if tr.submit_t <= t
                       and (tr.done_t is None or tr.done_t > t))

        ttft, gaps = readers.ttft_ms(rec), readers.gaps_ms(rec)
        waits = [q * 1e3 for q in rec.queue_wait_s]
        core.say(
            "rate", rate_rps=rate, arrivals=len(ttft),
            failed=sum(tr.req.status == "failed" for tr in rec.tracked),
            in_flight=[in_flight(q) for q in (0.0, 0.25, 0.5, 0.75, 1.0)],
            queue_wait_p50_ms=readers.percentile_or_none(waits, 50),
            queue_wait_max_ms=max(waits, default=None),
            ttft_mean_ms=sum(ttft) / len(ttft) if ttft else None,
            ttft_p50_ms=readers.percentile_or_none(ttft, 50),
            ttft_p95_ms=readers.percentile_or_none(ttft, 95),
            itl_p95_ms=readers.percentile_or_none(gaps, 95),
            gap_mean_ms=sum(gaps) / len(gaps) if gaps else None,
            out_tokens_per_s=sum(s[4] for s in rec.steps) / args.seconds,
            mixed_step_ms=readers.step_ms(rec, "mixed"),
            decode_step_ms=readers.step_ms(rec, "decode"),
            mixed_steps=len(readers.window_steps(rec, "mixed")),
            decode_steps=len(readers.window_steps(rec, "decode")),
            kv_live_peak=max(rec.kv_live, default=None),
            preemptions=rec.counters["preemptions"],
            compilations=compiles.n,
            healthy=served.sound(served.health()))
        carried = out["active"]
        for tr in carried:
            tr.standing = True      # the next leg takes no wait from it
    return 0


if __name__ == "__main__":
    sys.exit(main())
