#!/usr/bin/env python3
"""python3 perfbench/trace_summary.py <trace dir>

Prints what a recorded ``.xplane.pb`` holds: planes, their lines, how many
events each has and the names that took most time. For looking at a trace by
hand before a reader's pattern is trusted."""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    from perfbench import xplane

    path = (argv or sys.argv[1:])[0]
    profile = xplane.load(path)
    reduced = xplane.reduce_profile(profile)
    for name, secs in sorted(reduced["ops_s"].items(),
                             key=lambda kv: -kv[1])[:25]:
        print(json.dumps({"self_s": round(secs, 6), "op": name[:1500]}))
    print(json.dumps({k: reduced[k] for k in ("busy_s", "window_s",
                                              "modules_s", "breakdown")}))
    for plane in profile["planes"]:
        for line in plane["lines"]:
            by_name = {}
            for name, _, d in line["events"]:
                by_name[name] = by_name.get(name, 0.0) + d / 1e9
            top = sorted(by_name.items(), key=lambda kv: -kv[1])[:12]
            print(json.dumps({"plane": plane["name"], "line": line["name"],
                              "events": len(line["events"]),
                              "top": [[n[:90], round(s, 6)] for n, s in top]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
