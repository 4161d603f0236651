"""Device time per solve, in ms, of the programs launched inside the program's
``spar_sink.sketch`` span: the draw, the gathered costs, the duplicate merge
and sort, dispatched op by op by the eager solve."""
from bench import phase_trace


def read(run):
    return phase_trace.per_solve(run, __file__, "span_device_s", "spar_sink.sketch", 1e3)
