"""refresh_share.engine: the cut refresh's share of the device's op time,
in the engine cells: the op time of the operations under the program's
`cut_refresh` name scope over the op time of all operations in the
traced window (`lib/program_trace.py`, `scope_s`; a loop or conditional
holding its body counts in neither).  A trace without the scope reads
nothing.  Moves `fed_iters_per_s`."""
from lib import program_trace


def read(ctx):
    prog = program_trace.of(ctx)
    total = sum(ctx["trace"]["op_s"].values())
    if not prog or prog["scope_s"]["cut_refresh"] <= 0 or total <= 0:
        return None
    return 100.0 * prog["scope_s"]["cut_refresh"] / total
