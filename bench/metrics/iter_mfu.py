"""iter_mfu: the whole AFTO master iteration's share of the chip's peak
on its binding roof, in the engine cells.

The work per iteration is the benchmark's count (`lib/work.py`,
`afto_iteration_work`): bytes and operations of the cut-matrix passes
of the step, the refresh and the gap record.  The share is the larger
of achieved FLOP/s over peak FLOP/s and achieved bytes/s over peak HBM
bytes/s, over the traced window.  Moves `fed_iters_per_s`."""


def read(ctx):
    work, tr, pk = ctx["work"], ctx["trace"], ctx["peaks"]
    if not work.get("iterations") or tr["window_s"] <= 0:
        return None
    chips = ctx["device"]["count"]
    per_s = work["iterations"] / tr["window_s"]
    flops = per_s * work["flops_per_iter"] / (pk["bf16_flops"] * chips)
    hbm = per_s * work["bytes_per_iter"] / (pk["hbm_bytes_per_s"] * chips)
    return 100.0 * max(flops, hbm)
