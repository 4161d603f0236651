"""Device programs per solve launched inside the program's
``spar_sink.sketch`` span: one per primitive while the sketch build runs
eagerly."""
from bench import phase_trace


def read(run):
    return phase_trace.per_solve(run, __file__, "span_programs", "spar_sink.sketch", 1.0)
