"""host_path_ms.engine: the host's part of one `repro.core.run` call, in
the engine cells: the mean over the window's `afto.run` spans of the
span less the `afto.wait` inside it (the device running the
trajectory), from the program's own spans in the trace
(`lib/program_trace.py`).  What is left is the schedule, the initial
state, the cache lookup, staging, dispatch and the history fetch.  A
trace without the program's spans reads nothing.  Moves
`fed_iters_per_s`."""
from lib import program_trace


def read(ctx):
    prog = program_trace.of(ctx)
    if not prog or not prog["runs"]:
        return None
    return 1e3 * sum(run - wait for run, wait in prog["runs"]) \
        / len(prog["runs"])
