#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

The cell, its configuration, traffic mix, correctness limits and
per-layer metric readers are all found by name from `BENCHMARK.json`:

    bench/configs/<config>.json   sizes, and <config>.py its plain reference
    bench/traffic/<traffic>.json  the traffic mix's parameters
    bench/limits/<workload>.json  the limit of each number compared
    bench/runners/<runner>.py     the runner the configuration names
    bench/metrics/<metric>.py     one reader per per-layer metric

With `--trace 0` the last line of standard output is the result with the
cell's end-to-end metrics; with `--trace 1` its per-layer metrics, the
device's busy and window seconds and a breakdown, from a profiler trace
of a window of at most `TRACE_WINDOW_S`.  Without a TPU, or with fewer
chips than the cell asks for, it exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(CHECKOUT, "src")]

from lib import common  # noqa: E402


# The longest window a traced run traces.  A traced 30 s window of the
# solve cell wrote a 455 MB trace and the whole run took 325-345 s on a
# v5e, against the 360 s a run may take; the per-layer metrics are
# shares and rates, which a shorter window reads alike.
TRACE_WINDOW_S = 10.0


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def load_cell(name: str):
    """(benchmark, cell, config, traffic, limits) of a workload name."""
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {c["name"]: c for c in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    config = common.load_json("configs", cell["config"] + ".json")
    traffic = common.load_json("traffic", cell["traffic"] + ".json")
    limits = common.load_json("limits", name + ".json")
    return bench, cell, config, traffic, limits


def metrics_of(entries, cell_name):
    return [m for m in entries
            if cell_name in m.get("workloads", [cell_name])]


def main(argv=None, find_devices=None, overrides=None) -> dict:
    """One run.  `find_devices` replaces the look for a chip and
    `overrides` adds to the runner's context: both only for the
    benchmark's own tests."""
    args = parse(argv)
    bench, cell, config, traffic, limits = load_cell(args.workload)
    import jax

    common.enable_compile_cache()
    devices = (find_devices or common.require_chips)(cell["chips"])
    runner = common.load_module("runners", config["runner"] + ".py")

    trace_dir = os.path.join(CHECKOUT, ".bench_trace", args.workload)
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        tracer = lambda: jax.profiler.trace(trace_dir)  # noqa: E731
        annotate = jax.profiler.TraceAnnotation
    else:
        tracer = contextlib.nullcontext
        annotate = lambda name: contextlib.nullcontext()  # noqa: E731

    seconds = min(args.seconds, TRACE_WINDOW_S) if args.trace \
        else args.seconds
    ctx = {"config": config, "traffic": traffic, "seed": args.seed,
           "seconds": seconds, "trace": bool(args.trace),
           "t_start": T_START, "tracer": tracer, "annotate": annotate,
           "device_record": lambda: common.device_record(devices)}
    ctx.update(overrides or {})
    res = runner.run(ctx)

    device = res["device"]
    if args.trace:
        from lib import peaks, trace

        red = trace.reduce_events(trace.load_events(trace_dir))
        rctx = {"trace": red, "work": res["work"], "device": device,
                "peaks": peaks.peaks(device["kind"])}
        metrics = {}
        for m in metrics_of(bench["per_layer"], cell["name"]):
            reader = common.load_module("metrics", m["name"] + ".py")
            value = reader.read(rctx)
            if value is None:
                # the cell lists this metric, so its reader must find it
                common.log(f"bench: per-layer metric {m['name']} found "
                           "nothing to read in this cell's trace; no result")
                sys.exit(4)
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device = dict(device, busy_s=red["busy_s"], window_s=red["window_s"])
        breakdown = {"device_ops": red["device_ops"],
                     "idle_gaps": red["idle_gaps"]}
    else:
        values = dict(res["e2e"], setup_s=res["setup_s"])
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in metrics_of(bench["end_to_end"], cell["name"])}
        breakdown = None

    numbers = res["numbers"]
    result = {"correct": common.is_correct(numbers, limits),
              "attempted": res["attempted"], "failed": res["failed"],
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["check"] = common.check_line(numbers, limits)
    common.emit(result)
    return result


if __name__ == "__main__":
    main()
