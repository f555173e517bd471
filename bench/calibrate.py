#!/usr/bin/env python3
"""Readings behind a cell's correctness limits, on the chip.

    python3 bench/calibrate.py --workload <name> --seeds 101-112 \
        --control-seeds 201-203 [--out results.jsonl]

Prints one JSON line per reading: the program's solve against the
plain reference on each of `--seeds`, and on each of `--control-seeds`
the control (the reference in bfloat16) put in the program's place.
The limits in `bench/limits/<workload>.json` are set from these
readings, as `PERF.md` records.  The benchmark's runs do not run this.
"""
import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]

import run  # noqa: E402
from lib import common  # noqa: E402


def seed_list(text: str):
    out = []
    for part in filter(None, text.split(",")):
        a, _, b = part.partition("-")
        out += list(range(int(a), int(b or a) + 1))
    return out


def main(argv=None, find_devices=None, cell=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    _, cell_, config, traffic, _ = cell or run.load_cell(args.workload)
    common.enable_compile_cache()
    (find_devices or common.require_chips)(cell_["chips"])
    runner = common.load_module("runners", config["runner"] + ".py")
    out = open(args.out, "a") if args.out else None
    rows = []

    def emit(row):
        row = dict(row, workload=args.workload)
        rows.append(row)
        line = json.dumps(row)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    runner.calibrate({"config": config, "traffic": traffic},
                     seed_list(args.seeds), seed_list(args.control_seeds),
                     emit)
    return rows


if __name__ == "__main__":
    main()
