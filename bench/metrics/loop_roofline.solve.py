"""The iteration loop's share of its roofline, in %: the least time the
window's iterations need on this chip (`bench.roofline`, from each solve's
live nnz and iteration count) over the loop program's device time."""
import re

from bench import roofline

LOOP = re.compile(r"(^|_)while(\.\d+)?$")


def read(run):
    calls = run.record.get("calls")
    if not run.trace or not calls:
        return None
    t = sum(v for name, v in run.trace["programs"].items() if LOOP.search(name))
    if t <= 0:
        return None
    n = run.params["n"]
    least = sum(roofline.least_time(c["nnz"], n, n, c["n_iter"], run.device_kind)[0]
                for c in calls)
    return 100.0 * least / t
