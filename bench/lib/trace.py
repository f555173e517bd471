"""From a profiler trace to the benchmark's numbers.

`load_events` reads the `.xplane.pb` that `jax.profiler` writes and keeps
two kinds of event: the operations on each device's op line, and the
harness's own host spans (`jax.profiler.TraceAnnotation` around each
call into the program).  `reduce_events` turns them into

- `window_s`: the length of the harness's `window` span;
- `busy_s`: the union of the intervals in which an operation ran on a
  device, inside the window, averaged over the devices;
- `device_ops`: the operations that took most device time, summed by
  name;
- `idle_gaps`: the longest stretches inside the window in which no
  operation ran, each named by the host span that covers most of it;
- `op_s`: device seconds by operation name, for kernel times.

Both planes are on the profiler's one clock.
"""
from __future__ import annotations

import glob
import os

DEVICE_PREFIX = "/device:"
OP_LINE = "XLA Ops"
HOST_SPANS = ("window", "solve", "sample")


def load_events(trace_dir: str) -> list:
    """[{plane, line, name, start_ns, dur_ns}] of the newest trace under
    `trace_dir`: device op events and harness spans only."""
    import jax

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no trace under {trace_dir}")
    planes = list(jax.profiler.ProfileData.from_file(paths[-1]).planes)
    # where any device plane has an op line, planes without one (system
    # or host-side planes of the device) hold no ops of the program
    has_ops = any(ln.name == OP_LINE for p in planes
                  if p.name.startswith(DEVICE_PREFIX) for ln in p.lines)
    out = []
    for plane in planes:
        device = plane.name.startswith(DEVICE_PREFIX)
        lines = list(plane.lines)
        if device and has_ops:
            lines = [ln for ln in lines if ln.name == OP_LINE]
        elif device:
            # a trace that names no op line: each device's busiest line
            lines = sorted(
                (ln for ln in lines if ln.name not in ("Steps",
                                                       "XLA Modules")),
                key=lambda ln: -len(list(ln.events)))[:1]
        for line in lines:
            for ev in line.events:
                if not device and ev.name not in HOST_SPANS:
                    continue
                out.append({"plane": plane.name, "line": line.name,
                            "name": ev.name, "start_ns": int(ev.start_ns),
                            "dur_ns": int(ev.duration_ns)})
    return out


def _union(intervals):
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def _clip(a, b, lo, hi):
    return max(a, lo), min(b, hi)


def reduce_events(events: list, top: int = 10) -> dict:
    windows = [e for e in events if e["name"] == "window"
               and not e["plane"].startswith(DEVICE_PREFIX)]
    if not windows:
        raise ValueError("the trace holds no harness window span")
    w = max(windows, key=lambda e: e["dur_ns"])
    lo, hi = w["start_ns"], w["start_ns"] + w["dur_ns"]
    ops = [e for e in events if e["plane"].startswith(DEVICE_PREFIX)]
    planes = sorted({e["plane"] for e in ops})
    if not planes:
        raise ValueError("the trace holds no device operation")

    busy, per_plane_union = [], {}
    op_ns = {}
    for p in planes:
        iv = []
        mine = sorted((e for e in ops if e["plane"] == p),
                      key=lambda e: (e["start_ns"], -e["dur_ns"]))
        for i, e in enumerate(mine):
            a, b = _clip(e["start_ns"], e["start_ns"] + e["dur_ns"], lo, hi)
            if b <= a:
                continue
            iv.append((a, b))
            # an op whose interval holds the next one (a loop around its
            # body) counts toward busy time, not toward op time
            end = e["start_ns"] + e["dur_ns"]
            nxt = mine[i + 1] if i + 1 < len(mine) else None
            if nxt is None or not (nxt["start_ns"] < end and nxt["start_ns"]
                                   + nxt["dur_ns"] <= end):
                op_ns[e["name"]] = op_ns.get(e["name"], 0) + (b - a)
        u = _union(iv)
        per_plane_union[p] = u
        busy.append(sum(b - a for a, b in u))

    spans = [e for e in events if not e["plane"].startswith(DEVICE_PREFIX)
             and e["name"] != "window"]
    gaps = []
    u = per_plane_union[planes[0]]
    edges = [lo] + [x for ab in u for x in ab] + [hi]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b > a:
            gaps.append((b - a, _cover(spans, a, b)))
    gaps.sort(key=lambda g: -g[0])
    n_dev = len(planes)
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy) / n_dev / 1e9,
        "devices": n_dev,
        "device_ops": [[k, v / n_dev / 1e9] for k, v in sorted(
            op_ns.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[name, ns / 1e9] for ns, name in gaps[:top]],
        "op_s": {k: v / n_dev / 1e9 for k, v in op_ns.items()},
    }


def _cover(spans, a, b) -> str:
    """The host span that overlaps [a, b) most, or "untraced host"."""
    best, name = 0, "untraced host"
    for s in spans:
        x, y = _clip(s["start_ns"], s["start_ns"] + s["dur_ns"], a, b)
        if y - x > best:
            best, name = y - x, s["name"]
    return name
