"""Device idle time per solve, in ms, inside the program's ``spar_sink.sketch``
span: what dispatching the eager sketch build op by op from the host costs
the device."""
from bench import phase_trace


def read(run):
    return phase_trace.per_solve(run, __file__, "span_idle_s", "spar_sink.sketch", 1e3)
