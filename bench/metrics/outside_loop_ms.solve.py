"""Device time per solve, in ms, of every program in the solves' spans other
than the iteration loop: the sketch draw, the gathered costs, the duplicate
merge and sort, the objective."""
import re

LOOP = re.compile(r"(^|_)while(\.\d+)?$")


def read(run):
    calls = run.record.get("calls")
    if not run.trace or not calls or not run.trace["programs"]:
        return None
    other = sum(t for name, t in run.trace["programs"].items() if not LOOP.search(name))
    return 1e3 * other / len(calls)
