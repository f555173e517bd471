"""Aggregate the dry-run JSONL into the §Roofline table (markdown +
summary CSV rows)."""
from __future__ import annotations

import glob
import json
import os
import time


def _recompute_terms(r: dict) -> dict:
    """Re-derive terms from the stored raw fields so formula fixes apply
    to existing JSONL without re-compiling."""
    if r.get("status") != "ok":
        return r
    from repro.launch.mesh import TARGET_DEVICE_KIND, chip_peaks
    pk = chip_peaks(TARGET_DEVICE_KIND)
    chips = r["chips"]
    flops_total = r["hlo_flops_per_dev"] * chips
    flops_corr = max(flops_total, r["analytic_flops_total"])
    coll = sum(v for k, v in r["coll_bytes"].items() if k != "count")
    r = dict(r)
    r["compute_s"] = flops_total / (chips * pk["flops_bf16"])
    r["compute_corrected_s"] = flops_corr / (chips * pk["flops_bf16"])
    r["memory_s"] = r["hlo_bytes_per_dev"] / pk["hbm_bw"]
    r["collective_s"] = coll / (chips * pk["ici_bw"])
    r["useful_ratio"] = r["model_flops_total"] / max(flops_corr, 1.0)
    r["hbm_gb_per_dev"] = (r["arg_bytes"] + r["temp_bytes"]
                           + r["out_bytes"]) / 1e9
    kinds = {"compute": r["compute_corrected_s"],
             "memory": r["memory_s"], "collective": r["collective_s"]}
    r["dominant"] = max(kinds, key=kinds.get)
    return r


def load(paths=("results/dryrun_pod.jsonl", "results/dryrun_multipod.jsonl")):
    rows = []
    for p in paths:
        if os.path.exists(p):
            with open(p) as f:
                for line in f:
                    rows.append(_recompute_terms(json.loads(line)))
    return rows


def render_markdown(rows) -> str:
    hdr = ("| arch | shape | mesh | step | compute(ms) | memory(ms) | "
           "collective(ms) | dominant | 6ND/HLO | HBM GB/dev | status |")
    sep = "|" + "---|" * 11
    lines = [hdr, sep]
    for r in sorted(rows, key=lambda r: (r.get("mesh", ""), r["arch"],
                                         r["shape"])):
        if r.get("status") == "ok":
            lines.append(
                f"| {r['arch']} | {r['shape']} | {r['mesh']} "
                f"| {r['step_kind']} "
                f"| {r['compute_corrected_s']*1e3:.2f} "
                f"| {r['memory_s']*1e3:.2f} "
                f"| {r['collective_s']*1e3:.2f} "
                f"| {r['dominant']} "
                f"| {r['useful_ratio']:.2f} "
                f"| {r['hbm_gb_per_dev']:.1f} | ok |")
        else:
            lines.append(
                f"| {r['arch']} | {r['shape']} | {r.get('mesh','?')} | - "
                f"| - | - | - | - | - | - | {r.get('status')} |")
    return "\n".join(lines)


def main():
    t0 = time.perf_counter()
    rows = load()
    ok = [r for r in rows if r.get("status") == "ok"]
    skipped = [r for r in rows if r.get("status") == "skipped"]
    err = [r for r in rows if r.get("status") == "error"]
    doms = {}
    for r in ok:
        doms[r["dominant"]] = doms.get(r["dominant"], 0) + 1
    return [("roofline_table", (time.perf_counter() - t0) * 1e6,
             f"ok={len(ok)};skipped={len(skipped)};errors={len(err)};"
             + ";".join(f"{k}_bound={v}" for k, v in sorted(doms.items())))]


if __name__ == "__main__":
    for name, us, derived in main():
        print(f"{name},{us:.1f},{derived}")
    print()
    print(render_markdown(load()))
