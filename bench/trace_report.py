#!/usr/bin/env python3
"""Read a traced run's trace by the program's own spans and scopes.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace 1
    python3 bench/trace_report.py --workload <name> \
        [--slice out.json --solves 2]

Prints one JSON line: the cell's per-layer metrics from the trace that
`bench/run.py --trace 1` left under `.bench_trace/<name>` (those the
cell lists, and `host_path_ms.engine`, `refresh_share.engine` and
`cut_kernel_roofline.scope`), with device seconds by scope (`scope_s`),
the window's idle time by the innermost span covering it
(`idle_by_span`), seconds by program span, and the longest idle gaps
named by their spans (`lib/program_trace.py`).  `--slice` writes the
events of `--solves` consecutive solves from the middle of the window,
compactly, as test data.  Run it on the chip the traced run ran on:
the device's kind comes from there.  The benchmark's runs do not run
this.
"""
import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]

import run  # noqa: E402
from lib import common, peaks, program_trace, work  # noqa: E402

NEW = ("host_path_ms.engine", "refresh_share.engine",
       "cut_kernel_roofline.scope")


def report(workload, events, device, top=10):
    """(line, program reduction) of a cell's traced run from its events
    and its device record ({"kind", "count"})."""
    bench, cell, config, traffic, _ = run.load_cell(workload)
    prog = program_trace.reduce(events, top=top)
    lo, hi = program_trace._window(events)
    solves = sum(1 for e in events if e["name"] == "solve"
                 and lo <= e["start_ns"] < hi)
    runs = 1 if traffic["engine"] == "scan" \
        else traffic["seeds"] * len(traffic["eta_x"])
    wk = dict(work.afto_iteration_work(config, traffic),
              iterations=solves * runs * traffic["iterations"])
    ctx = {"trace": prog, "program": prog, "work": wk, "device": device,
           "peaks": peaks.peaks(device["kind"])}
    names = [m["name"] for m in run.metrics_of(bench["per_layer"],
                                               cell["name"])]
    metrics = {}
    for name in names + [n for n in NEW if n not in names]:
        value = common.load_module("metrics", name + ".py").read(ctx)
        if value is not None:
            metrics[name] = value
    line = {"workload": workload, "solves": solves, "metrics": metrics,
            "window_s": prog["window_s"], "busy_s": prog["busy_s"],
            "scope_s": prog["scope_s"], "idle_by_span": prog["idle_by_span"],
            "span_s": prog["span_s"], "idle_gaps": prog["idle_gaps"],
            "device_ops": prog["device_ops"]}
    return line, prog


def solve_slice(events, solves: int):
    """(lo, hi) around `solves` consecutive harness `solve` spans from the
    middle of the window."""
    lo, hi = program_trace._window(events)
    spans = sorted((e for e in events if e["name"] == "solve"
                    and lo <= e["start_ns"] < hi),
                   key=lambda e: e["start_ns"])
    k = max(0, len(spans) // 2 - solves // 2)
    pick = spans[k:k + solves]
    return pick[0]["start_ns"], pick[-1]["start_ns"] + pick[-1]["dur_ns"]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--slice", default=None)
    ap.add_argument("--solves", type=int, default=2)
    args = ap.parse_args(argv)
    import jax

    _, cell, _, _, _ = run.load_cell(args.workload)
    events = program_trace.load(os.path.join(
        common.CHECKOUT, ".bench_trace", args.workload))
    line, _ = report(args.workload, events,
                     {"kind": jax.devices()[0].device_kind,
                      "count": cell["chips"]})
    if args.slice:
        lo, hi = solve_slice(events, args.solves)
        with open(args.slice, "w") as f:
            json.dump(program_trace.to_slice(events, lo, hi), f)
        common.log(f"slice of {args.solves} solves: {args.slice}, "
                   f"{os.path.getsize(args.slice)} bytes")
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
