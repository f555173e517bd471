"""Roofline-term extraction from compiled dry-run artifacts.

Three terms per (arch x shape x mesh), in seconds (deliverable g),
over the peaks of the target chip (`launch.mesh.CHIP_PEAKS`, a TPU
v5e: 197 TFLOP/s bf16, 819 GB/s HBM, 50 GB/s per ICI link):

  compute    = HLO_FLOPs_total   / (chips * peak bf16 FLOP/s)
  memory     = HLO_bytes_total   / (chips * peak HBM bytes/s)
  collective = collective_bytes  / (chips * ICI link bytes/s)

Sourcing notes (measured behaviour of jax 0.8.2 / XLA CPU AOT):
  * `compiled.cost_analysis()` reports PER-DEVICE numbers after SPMD
    partitioning -> multiply by chips for the totals above.
  * a `lax.scan` body is counted ONCE regardless of trip count.  The
    dry-run therefore python-unrolls the layer loop; the remaining
    sequence-chunk scans (mamba / mLSTM chunks) are corrected with the
    analytic `step_flops` model, and we report both raw and corrected.
  * collective bytes are parsed from `compiled.as_text()`: the sum of
    output-shape bytes of every all-reduce / all-gather / reduce-scatter
    / all-to-all / collective-permute instruction (output size ~ operand
    size for all-reduce; for all-gather this upper-bounds the wire
    bytes).  Instructions inside while-loop bodies appear once; with the
    layer loop unrolled the only looped collectives are the small chunk
    scans, noted per-arch.
"""
from __future__ import annotations

import dataclasses
import json
import re
from typing import Dict, Optional

from repro.launch.mesh import TARGET_DEVICE_KIND, chip_peaks

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
    "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

# matches e.g. "f32[16,128]{1,0}" or "bf16[4096]"
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum output bytes per collective kind from HLO text."""
    out = {k: 0 for k in _COLLECTIVES}
    out["count"] = 0
    for line in hlo_text.splitlines():
        line = line.strip()
        m = re.match(r"%?[\w.\-]+ = (.*?) (%?[\w\-]+)\(", line)
        if not m:
            continue
        op = m.group(2).lstrip("%")
        # start ops appear as "all-reduce-start" etc.
        base = op.replace("-start", "")
        if base in _COLLECTIVES:
            out[base] += _shape_bytes(m.group(1))
            out["count"] += 1
    return out


@dataclasses.dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    chips: int
    step_kind: str
    # raw per-device numbers from cost_analysis
    hlo_flops_per_dev: float
    hlo_bytes_per_dev: float
    # memory_analysis (per device)
    arg_bytes: float
    temp_bytes: float
    out_bytes: float
    # HLO-text collectives (whole-program, already per-device SPMD module)
    coll_bytes: Dict[str, int]
    # analytic
    analytic_flops_total: float
    model_flops_total: float      # 6 * N_active * tokens

    def terms(self) -> Dict[str, float]:
        pk = chip_peaks(TARGET_DEVICE_KIND)
        flops_total = self.hlo_flops_per_dev * self.chips
        # scan-mode undercount correction: the analytic model is the
        # floor (see module docstring); useful_ratio uses the corrected
        # figure so scan rows don't report >1 "useful" compute.
        flops_corr = max(flops_total, self.analytic_flops_total)
        coll = sum(v for k, v in self.coll_bytes.items() if k != "count")
        return {
            "compute_s": flops_total / (self.chips * pk["flops_bf16"]),
            "compute_corrected_s":
                flops_corr / (self.chips * pk["flops_bf16"]),
            "memory_s": (self.hlo_bytes_per_dev * self.chips)
                / (self.chips * pk["hbm_bw"]),
            "collective_s": coll / (self.chips * pk["ici_bw"]),
            "useful_ratio": (self.model_flops_total
                             / max(flops_corr, 1.0)),
            "hbm_gb_per_dev": (self.arg_bytes + self.temp_bytes
                               + self.out_bytes) / 1e9,
        }

    def dominant(self) -> str:
        t = self.terms()
        kinds = {"compute": t["compute_corrected_s"],
                 "memory": t["memory_s"],
                 "collective": t["collective_s"]}
        return max(kinds, key=kinds.get)

    def to_json(self) -> dict:
        d = dataclasses.asdict(self)
        d.update(self.terms())
        d["dominant"] = self.dominant()
        return d


def build_report(*, arch: str, shape: str, mesh_name: str, chips: int,
                 step_kind: str, compiled, analytic_flops_total: float,
                 model_flops_total: float) -> RooflineReport:
    ca = compiled.cost_analysis() or {}
    ma = compiled.memory_analysis()
    txt = compiled.as_text()
    return RooflineReport(
        arch=arch, shape=shape, mesh=mesh_name, chips=chips,
        step_kind=step_kind,
        hlo_flops_per_dev=float(ca.get("flops", 0.0)),
        hlo_bytes_per_dev=float(ca.get("bytes accessed", 0.0)),
        arg_bytes=float(ma.argument_size_in_bytes),
        temp_bytes=float(ma.temp_size_in_bytes),
        out_bytes=float(ma.output_size_in_bytes),
        coll_bytes=collective_bytes(txt),
        analytic_flops_total=analytic_flops_total,
        model_flops_total=model_flops_total)
