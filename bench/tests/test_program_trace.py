"""The reduction by the program's own spans and scopes
(`lib/program_trace.py`) and the metrics that read it: on the
hand-made traces of `test_trace.py`, which it must reduce as
`trace.reduce_events` does; on a hand-made solve with nested program
spans and scoped operations, with known answers; and on a slice of a
trace recorded on a TPU v5e."""
import glob
import json
import os
import random

import pytest

from lib import program_trace, trace
from lib.common import load_module

DEV = "/device:TPU:0"
HOST = "/host:CPU"
DATA = os.path.join(os.path.dirname(__file__), "data")
V5E_SLICE = os.path.join(DATA, "v5e_rhpo_wine_solve.json")

STEP = "jit(scan_all)/while/body/closed_call/afto_step"
REFRESH = ("jit(scan_all)/while/body/closed_call/cond/branch_1_fun/"
           "cut_refresh/transpose(jvp())/while/body/closed_call")
KERNEL = "cut_kernel/cut_eval/pallas_call"


def ev(plane, name, start, dur, scope=None):
    e = {"plane": plane, "line": "XLA Ops" if plane.startswith("/device:")
         else "python", "name": name, "start_ns": start, "dur_ns": dur}
    if scope is not None:
        e["scope"] = scope
    return e


def hand_made():
    return [ev(HOST, "window", 1000, 10_000), ev(HOST, "solve", 1000, 500),
            ev(HOST, "solve", 1500, 6000), ev(HOST, "sample", 7500, 3500),
            ev(DEV, "fusion.1", 500, 1000), ev(DEV, "fusion.2", 2000, 2000),
            ev(DEV, "cut_eval", 3000, 2000), ev(DEV, "fusion.1", 9000, 3000)]


def a_loop():
    return [ev(HOST, "window", 0, 100), ev(DEV, "while", 0, 90),
            ev(DEV, "a", 10, 20), ev(DEV, "b", 40, 20)]


def two_devices():
    return [ev(HOST, "window", 0, 1000), ev(DEV, "a", 0, 1000),
            ev("/device:TPU:1", "a", 0, 500)]


def a_random_trace():
    rnd = random.Random(7)
    events = []
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
        events.append(ev(HOST, ("solve", "sample")[k // 50_000 % 2], k,
                         50_000))
    return events


@pytest.mark.parametrize("make", [hand_made, a_loop, two_devices,
                                  a_random_trace])
def test_without_program_spans_it_reduces_as_reduce_events(make):
    events = make()
    old = trace.reduce_events(events)
    new = program_trace.reduce(events)
    for k, v in old.items():
        assert new[k] == v, k
    assert new["runs"] == [] and new["span_s"] == {}
    assert new["scope_s"]["unscoped"] == pytest.approx(
        sum(old["op_s"].values()))
    lo, hi = program_trace._window(events)
    idle = (hi - lo) / 1e9 - _first_device_busy_s(events, lo, hi)
    assert sum(new["idle_by_span"].values()) == pytest.approx(idle)
    assert all("/" not in k for k in new["idle_by_span"])


def _first_device_busy_s(events, lo, hi):
    iv = [trace._clip(e["start_ns"], e["start_ns"] + e["dur_ns"], lo, hi)
          for e in events if e["plane"] == DEV]
    return sum(b - a for a, b in trace._union([x for x in iv
                                               if x[1] > x[0]])) / 1e9


def a_solve():
    """One solve: nested program spans, and scoped device ops under a
    loop op that holds them."""
    spans = [("afto.run", 10, 880), ("afto.schedule", 10, 50),
             ("afto.init_state", 60, 40), ("afto.build", 100, 10),
             ("afto.stage", 110, 40), ("afto.dispatch", 150, 50),
             ("afto.wait", 200, 600), ("afto.fetch", 800, 90)]
    return ([ev(HOST, "window", 0, 1000), ev(HOST, "solve", 0, 900),
             ev(HOST, "sample", 900, 50)]
            + [ev(HOST, n, a, d) for n, a, d in spans]
            + [ev(DEV, "while.1", 180, 600, "jit(scan_all)/while"),
               ev(DEV, "cut_eval.1", 180, 120,
                  f"{STEP}/jit(cut_eval)/{KERNEL}"),
               ev(DEV, "cut_eval.2", 300, 200,
                  f"{REFRESH}/transpose(jvp(jit(cut_eval)))/{KERNEL}"),
               ev(DEV, "fusion.3", 550, 150,
                  "jit(scan_all)/while/body/closed_call/cond/"
                  "branch_1_fun/gap_record/add"),
               ev(DEV, "copy.4", 700, 80, "jit(scan_all)/copy")])


def test_scopes_spans_and_gaps_of_a_solve():
    r = program_trace.reduce(a_solve())
    ns = {k: round(v * 1e9) for k, v in r["scope_s"].items()}
    # a kernel in the refresh counts toward both; the loop toward none
    assert ns == {"afto_step": 120, "cut_refresh": 200, "gap_record": 150,
                  "cut_kernel": 320, "unscoped": 80}
    assert sum(r["op_s"].values()) == pytest.approx(550e-9)
    idle = {k: round(v * 1e9) for k, v in r["idle_by_span"].items()}
    assert idle == {"solve": 20, "solve/afto.schedule": 50,
                    "solve/afto.init_state": 40, "solve/afto.build": 10,
                    "solve/afto.stage": 40, "solve/afto.dispatch": 30,
                    "solve/afto.wait": 20, "solve/afto.fetch": 90,
                    "sample": 50, "untraced host": 50}
    # the loop op keeps the device busy from 180 to 780
    assert sum(idle.values()) == 1000 - 600
    gaps = [(n, round(s * 1e9)) for n, s in r["idle_gaps"]]
    assert gaps == [("solve/afto.fetch", 220), ("solve/afto.schedule", 180)]
    assert [round(s * 1e9) for _, s in r["idle_gaps"]] == [
        round(s * 1e9) for _, s in trace.reduce_events(
            [e for e in a_solve() if not e["name"].startswith("afto.")]
        )["idle_gaps"]]
    assert r["runs"] == [pytest.approx([880e-9, 600e-9])]
    assert r["span_s"]["afto.wait"] == pytest.approx(600e-9)


def test_scope_names_take_the_transforms_off():
    names = program_trace.scope_names(
        f"{REFRESH}/transpose(jvp(jit(cut_eval)))/vmap(cut_kernel)/cut_eval")
    assert {"cut_refresh", "cut_kernel", "cut_eval", "while"} <= names
    assert not {"afto_step", "gap_record"} & names


def test_an_ops_instruction_is_read_from_its_event_name():
    for name in ("%cut_eval.144 = f32[8,1]{1,0:T(8,128)} custom-call(...)",
                 "cut_eval.144"):
        assert program_trace.INSTRUCTION.match(name).group(1) == \
            "cut_eval.144"


def _ctx(events, **work):
    prog = program_trace.reduce(events)
    return {"trace": prog, "program": prog, "device": {"count": 1},
            "peaks": {"hbm_bytes_per_s": 819e9},
            "work": dict({"iterations": 10,
                          "cut_kernel_bytes_per_iter": 819.0}, **work)}


def test_the_metric_readers():
    ctx = _ctx(a_solve())
    read = lambda m: load_module("metrics", m + ".py").read(ctx)  # noqa
    assert read("host_path_ms.engine") == pytest.approx((880 - 600) * 1e-6)
    assert read("refresh_share.engine") == pytest.approx(100 * 200 / 550)
    # least time 10 * 819 B / 819 GB/s = 1e-8 s over 320 ns of kernels
    assert read("cut_kernel_roofline.scope") == pytest.approx(
        100 * 1e-8 / 320e-9)


@pytest.mark.parametrize("metric", ["host_path_ms.engine",
                                    "refresh_share.engine",
                                    "cut_kernel_roofline.scope"])
def test_a_trace_without_the_programs_spans_reads_nothing(metric):
    reader = load_module("metrics", metric + ".py")
    assert reader.read(_ctx(hand_made())) is None


def test_readers_find_the_newest_traced_run(tmp_path, monkeypatch):
    old = tmp_path / "a" / "plugins" / "profile" / "1"
    new = tmp_path / "b" / "plugins" / "profile" / "2"
    for d in (old, new):
        d.mkdir(parents=True)
        (d / "h.xplane.pb").write_bytes(b"")
    os.utime(old / "h.xplane.pb", (1, 1))
    assert program_trace.newest_trace_dir(str(tmp_path)) == str(
        tmp_path / "b")
    assert program_trace.newest_trace_dir(str(tmp_path / "none")) is None

    events = a_solve()
    monkeypatch.setattr(program_trace, "newest_trace_dir", lambda: "d")
    monkeypatch.setattr(program_trace, "load", lambda d: events)
    red = program_trace.reduce(events)
    ctx = {"trace": red}
    assert program_trace.of(ctx) == red and ctx["program"] == red
    # a newest trace that is not this run's window is not read
    assert program_trace.of({"trace": dict(red, window_s=1.0)}) is None


def test_a_slice_round_trips_cut_to_its_window():
    events = a_solve()
    back = program_trace.from_slice(json.loads(json.dumps(
        program_trace.to_slice(events, 100, 600))))
    assert {e["name"] for e in back} >= {"cut_eval.1", "afto.wait",
                                         "window"}
    assert all(0 <= e["start_ns"] and e["start_ns"] + e["dur_ns"] <= 500
               for e in back)
    one = [e for e in back if e["name"] == "cut_eval.2"][0]
    assert one["scope"].endswith(KERNEL) and one["dur_ns"] == 200
    assert "scope" not in [e for e in back if e["name"] == "solve"][0]


def test_a_cpu_trace_keeps_the_programs_spans(tmp_path):
    """`load` on a real trace of a tiny scan solve: on the CPU no device
    plane holds ops, so only the host spans come back."""
    import jax

    from repro.core import run

    spec = _tiny_spec()
    run(spec)
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("window"):
            with jax.profiler.TraceAnnotation("solve"):
                run(spec)
    names = {e["name"] for e in program_trace.load(str(tmp_path))}
    assert {"window", "solve", "afto.run", "afto.dispatch",
            "afto.wait"} <= names
    assert not any(n.startswith("$") for n in names)
    # the trace keeps each program's HLO: the trajectory's op_names
    # carry the program's scopes, and the kernels' scope where they run
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    tables = program_trace.op_names(path)
    scopes = set().union(*(program_trace.scope_names(p) for t in
                           tables.values() for p in t.values()))
    assert {"afto_step", "cut_refresh", "gap_record"} <= scopes


def _tiny_spec():
    import jax.numpy as jnp

    from repro.core import RunSpec
    from repro.core.scheduler import StragglerConfig
    from repro.core.types import Hyper, TrilevelProblem

    def f(d, x1, x2, x3):
        return jnp.sum((x1 - x2 - d["b"]) ** 2 + x3 ** 2)

    data = {"b": jnp.ones((2, 2))}
    prob = TrilevelProblem(f1=f, f2=f, f3=f, data=data, n_workers=2,
                           x1_init=jnp.zeros(2), x2_init=jnp.zeros(2),
                           x3_init=jnp.zeros(2))
    hyper = Hyper(n_workers=2, s_active=1, tau=3, k_inner=1, p_max=2,
                  t_pre=3, t1=100, eta_x=0.05, eta_z=0.05, d1=2)
    return RunSpec(problem=prob, hyper=hyper, n_iterations=6,
                   scheduler=StragglerConfig(n_workers=2, s_active=1,
                                             tau=3))


def test_a_v5e_slice_finds_the_kernels_by_scope():
    """Two consecutive solves of `rhpo-wine.solve` traced on a TPU v5e
    (PERF.md, sections 3 and 5).  The `cut_kernel` scope holds the 12
    Mosaic calls.  `cut_kernel_roofline`'s match on `cut_eval` in an op's
    name times the same calls and, since a name on the v5e is the
    instruction's HLO text with its operands, also the slices that cut
    the vecmat kernels' padded output back to D: by those and nothing
    else the two differ.  The program's spans cover the idle time inside
    the harness's solves."""
    r = program_trace.reduce(program_trace.load_slice(V5E_SLICE))
    named = {n: s for n, s in r["op_s"].items() if "cut_eval" in n}
    calls = [n for n in named if "custom-call(" in n]
    assert len({n.split(" = ")[0] for n in calls}) == 12
    assert all(n.split(" = ")[0].startswith("%cut_eval.") for n in calls)
    unpad = sum(s for n, s in named.items() if " slice(" in n)
    assert 0 < unpad < sum(named[n] for n in calls)
    assert r["scope_s"]["cut_kernel"] == pytest.approx(
        sum(named.values()) - unpad, rel=1e-9)
    assert min(r["scope_s"][k] for k in program_trace.SCOPES) > 0
    in_solve = {k: s for k, s in r["idle_by_span"].items()
                if k.split("/")[0] == "solve"}
    spans = sum(s for k, s in in_solve.items() if "/afto." in k)
    assert spans >= 0.9 * sum(in_solve.values())
    assert len(r["runs"]) == 2


def test_the_report_reads_every_metric_of_a_cell():
    import trace_report

    line, _ = trace_report.report("rhpo-wine.solve", a_solve(),
                                  {"kind": "TPU v5 lite", "count": 1})
    assert line["solves"] == 1
    assert set(line["metrics"]) >= {"iter_mfu", "cut_kernel_roofline",
                                    "device_idle.engine",
                                    "host_path_ms.engine",
                                    "refresh_share.engine",
                                    "cut_kernel_roofline.scope"}
    assert line["idle_gaps"][0][0] == "solve/afto.fetch"
