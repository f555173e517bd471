"""The trace-to-metrics reduction, on hand-made traces with known
answers and on a random one checked by an independent sweep.  A slice
of a trace recorded on a TPU v5e joins them once a traced chip run has
been made (PERF.md, Open questions)."""
import pytest

from lib import trace

DEV = "/device:TPU:0"
HOST = "/host:CPU"


def ev(plane, name, start, dur, line=None):
    return {"plane": plane, "line": line or ("XLA Ops" if plane == DEV
                                             else "python"),
            "name": name, "start_ns": start, "dur_ns": dur}


def test_hand_made_trace():
    events = [
        ev(HOST, "window", 1000, 10_000),
        ev(HOST, "dispatch", 1000, 500),
        ev(HOST, "sync", 1500, 6000),
        ev(HOST, "schedule", 7500, 3500),
        ev(DEV, "fusion.1", 500, 1000),      # starts before the window
        ev(DEV, "fusion.2", 2000, 2000),
        ev(DEV, "cut_eval", 3000, 2000),     # overlaps fusion.2
        ev(DEV, "fusion.1", 9000, 3000),     # ends after the window
    ]
    r = trace.reduce_events(events)
    assert r["window_s"] == pytest.approx(10e-6)
    # busy: [1000,1500) + [2000,5000) + [9000,11000) = 500 + 3000 + 2000
    assert r["busy_s"] == pytest.approx(5.5e-6)
    assert r["op_s"]["fusion.1"] == pytest.approx(2.5e-6)
    assert r["op_s"]["cut_eval"] == pytest.approx(2e-6)
    assert r["device_ops"][0][0] == "fusion.1"
    # gaps: [1500,2000) in sync, [5000,9000) mostly sync (5000-7500)
    gaps = dict((round(s * 1e9), n) for n, s in r["idle_gaps"])
    assert gaps == {4000: "sync", 500: "sync"}


def test_a_loop_around_its_body_counts_as_busy_not_as_op_time():
    events = [ev(HOST, "window", 0, 100), ev(DEV, "while", 0, 90),
              ev(DEV, "a", 10, 20), ev(DEV, "b", 40, 20)]
    r = trace.reduce_events(events)
    assert r["busy_s"] == pytest.approx(90e-9)
    assert r["op_s"] == pytest.approx({"a": 20e-9, "b": 20e-9})


def test_two_devices_average():
    events = [ev(HOST, "window", 0, 1000),
              ev(DEV, "a", 0, 1000),
              ev("/device:TPU:1", "a", 0, 500)]
    r = trace.reduce_events(events)
    assert r["devices"] == 2
    assert r["busy_s"] == pytest.approx(750e-9)


def test_no_window_or_no_device_is_an_error():
    with pytest.raises(ValueError):
        trace.reduce_events([ev(DEV, "a", 0, 10)])
    with pytest.raises(ValueError):
        trace.reduce_events([ev(HOST, "window", 0, 10)])


def _sweep_busy_ns(events, lo, hi):
    """Busy time by a sweep over +1/-1 edges: an independent count."""
    edges = []
    for e in events:
        a, b = max(e["start_ns"], lo), min(e["start_ns"] + e["dur_ns"], hi)
        if b > a:
            edges += [(a, 1), (b, -1)]
    busy, depth, last = 0, 0, None
    for x, d in sorted(edges):
        if depth > 0:
            busy += x - last
        depth += d
        last = x
    return busy


def test_random_trace_against_a_sweep():
    """Many sequential ops on two devices with random gaps, and harness
    spans over them: busy time by the reduction equals an independent
    sweep over the op edges, op time sums to it, and the idle gaps fit
    in the idle time."""
    import random

    rnd = random.Random(7)
    events, t = [], 0
    for plane in (DEV, "/device:TPU:1"):
        t = 0
        for i in range(500):
            t += rnd.randint(0, 3000)
            d = rnd.randint(1, 5000)
            events.append(ev(plane, f"op{i % 7}", t, d))
            t += d
    lo, hi = 10_000, t - 10_000
    events.append(ev(HOST, "window", lo, hi - lo))
    for k in range(lo, hi, 50_000):
        events.append(ev(HOST, ("dispatch", "sync")[k // 50_000 % 2], k,
                         50_000))
    r = trace.reduce_events(events)
    ops = [e for e in events if e["plane"].startswith("/device:")]
    per_dev = [_sweep_busy_ns([e for e in ops if e["plane"] == p], lo, hi)
               for p in (DEV, "/device:TPU:1")]
    assert r["window_s"] == pytest.approx((hi - lo) / 1e9)
    assert r["busy_s"] == pytest.approx(sum(per_dev) / 2 / 1e9)
    assert sum(r["op_s"].values()) == pytest.approx(r["busy_s"])
    idle0 = (hi - lo - per_dev[0]) / 1e9
    assert sum(s for _, s in r["idle_gaps"]) <= idle0 * (1 + 1e-9)
    assert {n for n, _ in r["idle_gaps"]} <= {"dispatch", "sync"}
    assert len(r["device_ops"]) <= 10 and len(r["idle_gaps"]) <= 10



def test_cut_kernel_time_is_read_from_the_names_xla_gives_the_kernels():
    from lib.common import load_module

    reader = load_module("metrics", "cut_kernel_roofline.py")
    ctx = {"work": {"iterations": 10, "cut_kernel_bytes_per_iter": 819.0},
           "peaks": {"hbm_bytes_per_s": 819e9}, "device": {"count": 1},
           "trace": {"op_s": {"cut_eval.69": 2e-8,
                              "jvp_jit_cut_eval__.40": 3e-8,
                              "transpose_jvp_jit_cut_eval___.41": 5e-8,
                              "fusion.12": 1.0, "vmap__.3": 1.0}}}
    # least time 10 * 819 B / 819 GB/s = 1e-8 s over 1e-7 s of kernels
    assert reader.read(ctx) == pytest.approx(10.0)
    ctx["trace"]["op_s"] = {"fusion.12": 1.0, "vmap__.3": 1.0}
    assert reader.read(ctx) is None
