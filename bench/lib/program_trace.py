"""The program's own spans and scopes in a profiler trace.

`repro.core.run` marks each call with host spans: `afto.run` around the
whole call and, inside it, the compiled engines' phases `afto.schedule`,
`afto.init_state`, `afto.build`, `afto.stage`, `afto.dispatch`,
`afto.wait` and `afto.fetch`.  Its compiled trajectory carries the name
scopes `afto_step`, `cut_refresh`, `gap_record` and `cut_kernel` in each
operation's `op_name`.  `load` reads a trace as `trace.load_events`
does and keeps besides the program's spans and each device operation's
scope path; `reduce` returns what `trace.reduce_events` returns for the
same trace, computed by that function, and adds

- `idle_gaps`: the same gaps, each name followed by the program span
  that covers most of the gap (`solve/afto.wait`), where one does;
- `scope_s`: device seconds by scope: an operation counts toward every
  scope on its path, by the op time of `op_s` (a loop or conditional
  holding its body is busy time, not op time); `unscoped` holds the
  operations on none;
- `idle_by_span`: every idle nanosecond of the window (on the first
  device, as the gaps are), summed by the innermost harness span and the
  innermost program span covering it (`solve/afto.dispatch`; `solve`
  where no program span covers it; `untraced host` outside the
  harness's spans);
- `span_s`: seconds by program span name, over the spans in the window;
- `runs`: `[run_s, wait_s]` of each `afto.run` in the window, `wait_s`
  the `afto.wait` spans inside it.

A program without these spans and scopes gives empty `runs` and
`span_s` and puts all device time under `unscoped`.
"""
from __future__ import annotations

import glob
import json
import os
import re

from lib import trace

PROGRAM_PREFIX = "afto."
SCOPES = ("afto_step", "cut_refresh", "gap_record", "cut_kernel")
UNTRACED = "untraced host"
TRANSFORM = re.compile(r"^[\w.-]*\((.*)\)$")
# an op event's name is its HLO instruction, or that instruction's text
INSTRUCTION = re.compile(r"^%?([\w.-]+)")
PROGRAM_ID = re.compile(r"\((\d+)\)$")
MODULE_LINE = "XLA Modules"
METADATA_PLANE = "/host:metadata"


def _fields(buf, i=0, end=None):
    """(field number, value) of a serialized protocol buffer message: an
    int for a varint or fixed field, a (start, end) slice for a
    length-delimited one."""
    end = len(buf) if end is None else end
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 1:
            value, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif wire == 5:
            value, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"protocol buffer wire type {wire} at {i}")
        yield key >> 3, value


def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def op_names(path: str) -> dict:
    """{program id: {instruction: op_name}} from the HLO of every program
    that the trace's metadata plane keeps (XSpace.planes = 1; XPlane
    name = 2, event_metadata = 4, stat_metadata = 5; XEventMetadata
    name = 2, stats = 5; XStat metadata_id = 1, bytes_value = 6; HloProto
    hlo_module = 1; HloModuleProto computations = 3; HloComputationProto
    instructions = 2; HloInstructionProto name = 1, metadata = 7;
    OpMetadata op_name = 2).  A device op's trace event carries no
    op_name, so its scope path is found here."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())

    def text(ab):
        return bytes(buf[ab[0]:ab[1]]).decode("utf-8", "replace")

    def sub(ab, *numbers):
        """The slices of the fields `numbers`, one level each, in `ab`."""
        out = [ab]
        for n in numbers:
            out = [v for a in out for f, v in _fields(buf, *a) if f == n]
        return out

    out = {}
    for plane in sub((0, len(buf)), 1):
        names = sub(plane, 2)
        if not names or text(names[0]) != METADATA_PLANE:
            continue
        for meta in sub(plane, 4, 2):
            key = dict(_fields(buf, *meta)).get(2)
            m = PROGRAM_ID.search(text(key)) if key else None
            if m is None:
                continue
            table = {}
            for stat in sub(meta, 5):
                value = dict(_fields(buf, *stat)).get(6)
                if value is None:
                    continue
                for ins in sub(value, 1, 3, 2):
                    f = dict(_fields(buf, *ins))
                    name = f.get(1)
                    on = sub(f[7], 2) if 7 in f else []
                    if name and on:
                        table[text(name)] = text(on[0])
            out[int(m.group(1))] = table
    return out


def load(trace_dir: str) -> list:
    """`trace.load_events` of the newest trace under `trace_dir`, with the
    program's host spans, and a `scope` path on every device operation:
    the op_name of its instruction in the HLO of the program running on
    that device at the time (`op_names`; the program is the event of the
    device's `XLA Modules` line around the op), or "" where not found."""
    import bisect

    import jax

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no trace under {trace_dir}")
    programs = op_names(paths[-1])
    planes = list(jax.profiler.ProfileData.from_file(paths[-1]).planes)
    has_ops = any(ln.name == trace.OP_LINE for p in planes
                  if p.name.startswith(trace.DEVICE_PREFIX) for ln in p.lines)
    out, scope_of = [], {}
    for plane in planes:
        device = plane.name.startswith(trace.DEVICE_PREFIX)
        lines = list(plane.lines)
        modules = sorted(
            (int(ev.start_ns), int(ev.start_ns + ev.duration_ns),
             int(m.group(1)))
            for ln in lines if device and ln.name == MODULE_LINE
            for ev in ln.events for m in [PROGRAM_ID.search(ev.name)] if m)
        starts = [m[0] for m in modules]
        if device and has_ops:
            lines = [ln for ln in lines if ln.name == trace.OP_LINE]
        elif device:
            lines = sorted(
                (ln for ln in lines if ln.name not in ("Steps",
                                                       MODULE_LINE)),
                key=lambda ln: -len(list(ln.events)))[:1]
        for line in lines:
            for ev in line.events:
                name = ev.name
                e = {"plane": plane.name, "line": line.name, "name": name,
                     "start_ns": int(ev.start_ns),
                     "dur_ns": int(ev.duration_ns)}
                if device:
                    if name not in scope_of:
                        k = bisect.bisect_right(starts, e["start_ns"]) - 1
                        table = programs.get(modules[k][2], {}) \
                            if k >= 0 and e["start_ns"] < modules[k][1] \
                            else {}
                        m = INSTRUCTION.match(name)
                        scope_of[name] = table.get(m.group(1), "") \
                            if m else ""
                    e["scope"] = scope_of[name]
                elif not (name in trace.HOST_SPANS
                          or name.startswith(PROGRAM_PREFIX)):
                    continue
                out.append(e)
    return out


def scope_names(path: str) -> set:
    """The scope names on an op_name path, each with the transforms
    around it taken off (`transpose(jvp(cut_kernel))` is `cut_kernel`)."""
    names = set()
    for part in path.split("/"):
        m = TRANSFORM.match(part)
        while m:
            part = m.group(1)
            m = TRANSFORM.match(part)
        names.add(part)
    return names


def _window(events):
    w = max((e for e in events if e["name"] == "window"
             and not e["plane"].startswith(trace.DEVICE_PREFIX)),
            key=lambda e: e["dur_ns"])
    return w["start_ns"], w["start_ns"] + w["dur_ns"]


def _gaps(events, lo, hi):
    """[(ns, a, b)] of the idle stretches of the window on the first
    device, longest first: `trace.reduce_events`'s gaps, with places."""
    first = min(e["plane"] for e in events
                if e["plane"].startswith(trace.DEVICE_PREFIX))
    iv = []
    for e in events:
        if e["plane"] == first:
            a, b = trace._clip(e["start_ns"], e["start_ns"] + e["dur_ns"],
                               lo, hi)
            if b > a:
                iv.append((a, b))
    edges = [lo] + [x for ab in trace._union(iv) for x in ab] + [hi]
    gaps = [(b - a, a, b) for a, b in zip(edges[0::2], edges[1::2])
            if b > a]
    gaps.sort(key=lambda g: -g[0])
    return gaps


def _labelled(spans, lo, hi):
    """[(a, b, harness, program)]: the window cut where a span starts or
    ends, each piece with the innermost (shortest) harness span and
    program span covering it, or None."""
    cuts = {lo, hi}
    for s in spans:
        for x in (s["start_ns"], s["start_ns"] + s["dur_ns"]):
            if lo < x < hi:
                cuts.add(x)
    cuts = sorted(cuts)
    starts = sorted(spans, key=lambda s: s["start_ns"])
    out, active, k = [], [], 0
    for a, b in zip(cuts, cuts[1:]):
        while k < len(starts) and starts[k]["start_ns"] <= a:
            active.append(starts[k])
            k += 1
        active = [s for s in active if s["start_ns"] + s["dur_ns"] > a]
        inner = {}
        for s in active:
            kind = s["name"].startswith(PROGRAM_PREFIX)
            if kind not in inner or s["dur_ns"] < inner[kind]["dur_ns"]:
                inner[kind] = s
        out.append((a, b, inner[False]["name"] if False in inner else None,
                    inner[True]["name"] if True in inner else None))
    return out


def _idle_by_piece(gaps, pieces, top):
    """{(harness, program): idle ns} over the gaps, and the same for each
    of the first `top` gaps."""
    total, per_gap = {}, [dict() for _ in gaps[:top]]
    order = sorted(range(len(gaps)), key=lambda i: gaps[i][1])
    j = 0
    for i in order:
        _, a, b = gaps[i]
        while j < len(pieces) and pieces[j][1] <= a:
            j += 1
        m = j
        while m < len(pieces) and pieces[m][0] < b:
            x, y = max(a, pieces[m][0]), min(b, pieces[m][1])
            if y > x:
                key = pieces[m][2:]
                total[key] = total.get(key, 0) + (y - x)
                if i < top:
                    per_gap[i][key] = per_gap[i].get(key, 0) + (y - x)
            m += 1
    return total, per_gap


def _label(harness, program):
    harness = harness or UNTRACED
    return harness if program is None else f"{harness}/{program}"


def reduce(events: list, top: int = 10) -> dict:
    base = [e for e in events if e["plane"].startswith(trace.DEVICE_PREFIX)
            or not e["name"].startswith(PROGRAM_PREFIX)]
    out = trace.reduce_events(base, top=top)
    lo, hi = _window(events)
    spans = [e for e in events if not e["plane"].startswith(
        trace.DEVICE_PREFIX) and e["name"] != "window"
        and e["start_ns"] < hi and e["start_ns"] + e["dur_ns"] > lo]
    gaps = _gaps(events, lo, hi)
    total, per_gap = _idle_by_piece(gaps, _labelled(spans, lo, hi), top)

    named = []
    for (old, s), part in zip(out["idle_gaps"], per_gap):
        prog = {}
        for (_, program), ns in part.items():
            if program is not None:
                prog[program] = prog.get(program, 0) + ns
        named.append([old if not prog else
                      f"{old}/{max(prog, key=prog.get)}", s])

    paths = {e["name"]: e.get("scope", "") for e in events
             if e["plane"].startswith(trace.DEVICE_PREFIX)}
    scope_s = dict.fromkeys(SCOPES + ("unscoped",), 0.0)
    for name, s in out["op_s"].items():
        on = scope_names(paths.get(name, "")) & set(SCOPES)
        for sc in on or ("unscoped",):
            scope_s[sc] += s

    inside = [s for s in spans if s["start_ns"] >= lo
              and s["start_ns"] + s["dur_ns"] <= hi
              and s["name"].startswith(PROGRAM_PREFIX)]
    span_s = {}
    for s in inside:
        span_s[s["name"]] = span_s.get(s["name"], 0) + s["dur_ns"] / 1e9
    runs = []
    for r in (s for s in inside if s["name"] == "afto.run"):
        a, b = r["start_ns"], r["start_ns"] + r["dur_ns"]
        wait = sum(s["dur_ns"] for s in inside if s["name"] == "afto.wait"
                   and s["plane"] == r["plane"] and s["line"] == r["line"]
                   and a <= s["start_ns"] and s["start_ns"] + s["dur_ns"]
                   <= b)
        runs.append([r["dur_ns"] / 1e9, wait / 1e9])

    return dict(out, idle_gaps=named, scope_s=scope_s,
                idle_by_span={_label(*k): ns / 1e9 for k, ns in sorted(
                    total.items(), key=lambda kv: -kv[1])},
                span_s=span_s, runs=runs)


# ---------------------------------------------------------------------------
# for the per-layer metric readers
# ---------------------------------------------------------------------------

def newest_trace_dir(root: str = None):
    """The cell directory under `<checkout>/.bench_trace` that holds the
    newest trace, as `bench/run.py --trace 1` leaves it; None if none."""
    from lib import common

    root = root or os.path.join(common.CHECKOUT, ".bench_trace")
    paths = glob.glob(os.path.join(root, "*", "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        return None
    newest = max(paths, key=os.path.getmtime)
    return newest.split(os.sep + "plugins" + os.sep)[0]


def of(ctx: dict):
    """The program reduction for a metric reader, kept in `ctx` for the
    next reader: the reduction of the newest traced run's trace, if its
    window is the one `ctx["trace"]` was reduced from; else None."""
    if "program" not in ctx:
        d = newest_trace_dir()
        red = None if d is None else reduce(load(d))
        if red is not None and abs(red["window_s"]
                                   - ctx["trace"]["window_s"]) > 1e-9:
            red = None
        ctx["program"] = red
    return ctx["program"]


# ---------------------------------------------------------------------------
# a recorded slice of a trace, kept as test data
# ---------------------------------------------------------------------------

def to_slice(events: list, lo: int, hi: int) -> dict:
    """The events that overlap [lo, hi), cut to it, compactly: one group
    of columns a plane and line, each name and its scope once, starts as
    steps from the one before."""
    groups, last, names, scopes, index = {}, {}, [], [], {}
    for e in sorted(events, key=lambda e: e["start_ns"]):
        a, b = trace._clip(e["start_ns"], e["start_ns"] + e["dur_ns"],
                           lo, hi)
        if b <= a:
            continue
        key = (e["name"], e.get("scope"))
        if key not in index:
            index[key] = len(names)
            names.append(e["name"])
            scopes.append(e.get("scope"))
        where = (e["plane"], e["line"])
        g = groups.setdefault(where, {"plane": e["plane"], "line": e["line"],
                                      "name": [], "start": [], "dur": []})
        g["name"].append(index[key])
        g["start"].append(a - last.get(where, lo))
        g["dur"].append(b - a)
        last[where] = a
    return {"names": names, "scopes": scopes, "groups": list(groups.values())}


def from_slice(data: dict) -> list:
    out = []
    for g in data["groups"]:
        t = 0
        for n, step, dur in zip(g["name"], g["start"], g["dur"]):
            t += step
            e = {"plane": g["plane"], "line": g["line"],
                 "name": data["names"][n], "start_ns": t, "dur_ns": dur}
            if data["scopes"][n] is not None:
                e["scope"] = data["scopes"][n]
            out.append(e)
    return out


def load_slice(path: str) -> list:
    with open(path) as f:
        return from_slice(json.load(f))
