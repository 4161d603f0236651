"""Device time of the iteration loop per iteration, in ms: the summed
device time of the loop's program (the eager ``solve()`` runs the sketched
Sinkhorn iteration as one ``while`` program) in the traced window, over the
iterations of the window's solves."""
import re

LOOP = re.compile(r"(^|_)while(\.\d+)?$")


def loop_seconds(trace):
    return sum(t for name, t in trace["programs"].items() if LOOP.search(name))


def read(run):
    calls = run.record.get("calls")
    if not run.trace or not calls:
        return None
    t, iters = loop_seconds(run.trace), sum(c["n_iter"] for c in calls)
    return 1e3 * t / iters if t > 0 and iters else None
