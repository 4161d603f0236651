"""Device time per solve, in ms, of the programs launched inside the program's
``spar_sink.objective`` span, wherever they ran: the objective from the
potentials and the gathered costs."""
from bench import phase_trace


def read(run):
    return phase_trace.per_solve(run, __file__, "span_device_s", "spar_sink.objective", 1e3)
