"""cut_kernel_roofline: the Mosaic cut kernels' share of their roofline,
in the engine cells.

The least time is the bytes of the forward cut evaluations the
algorithm makes in the traced window (`lib/work.py`,
`cut_kernel_bytes_per_iter`) over peak HBM bytes/s (the passes are
mat-vecs, so bytes bind); the kernels' transposed products are in the
kernel time but not in the count, so the share is a lower bound.  The
kernel time is the device time of the trace's events whose names hold
`cut_eval`: XLA names each Mosaic call of `kernels/cut_eval.py` after
the jitted `cut_eval` around it (`cut_eval.69`, `jvp_jit_cut_eval__.40`,
`transpose_jvp_jit_cut_eval___.41` in the scan solve's program compiled
for a v5e: its 12 `tpu_custom_call`s, and nothing else).  Under the
sweep's `vmap` they are named `vmap__.N` instead, so the grid cell does
not list this metric.  With no such event in the trace, nothing is
read, and the run fails.  Moves `fed_iters_per_s`."""

NAME = "cut_eval"


def read(ctx):
    work, tr, pk = ctx["work"], ctx["trace"], ctx["peaks"]
    kernel_s = sum(v for k, v in tr["op_s"].items()
                   if NAME in k)
    if kernel_s <= 0 or not work.get("iterations"):
        return None
    least_s = work["iterations"] * work["cut_kernel_bytes_per_iter"] \
        / (pk["hbm_bytes_per_s"] * ctx["device"]["count"])
    return 100.0 * least_s / kernel_s
