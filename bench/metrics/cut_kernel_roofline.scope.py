"""cut_kernel_roofline.scope: the Mosaic cut kernels' share of their
roofline, in the engine cells, with the kernels found by the program's
`cut_kernel` name scope, which holds the kernels' calls alone and keeps
through `vmap`, JVP and transpose (`lib/program_trace.py`, `scope_s`).
The least time is `cut_kernel_roofline`'s: the bytes of the forward cut
evaluations the algorithm makes in the window (`lib/work.py`) over peak
HBM bytes/s; the transposed products are timed but not counted, so the
share is a lower bound.  A trace without the scope reads nothing.
Moves `fed_iters_per_s`."""
from lib import program_trace


def read(ctx):
    work, pk = ctx["work"], ctx["peaks"]
    prog = program_trace.of(ctx)
    if not prog or prog["scope_s"]["cut_kernel"] <= 0 \
            or not work.get("iterations"):
        return None
    least_s = work["iterations"] * work["cut_kernel_bytes_per_iter"] \
        / (pk["hbm_bytes_per_s"] * ctx["device"]["count"])
    return 100.0 * least_s / prog["scope_s"]["cut_kernel"]
