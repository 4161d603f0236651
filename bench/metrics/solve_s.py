"""Seconds per call of the entry: the window, from its start to the last
solve's value on the host, over the solves completed. Solves run back to
back, each timed from the call (its problem already on the device) to its
value on the host; none starts after the window's last second."""


def read(run):
    calls = run.record.get("calls")
    if not calls:
        return None
    return calls[-1]["end"] / len(calls)
