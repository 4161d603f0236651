"""Mean iterations per solve (`Solution.n_iter`): where the solver's
stopping rule stopped. A count."""


def read(run):
    calls = run.record.get("calls")
    if not calls:
        return None
    return sum(c["n_iter"] for c in calls) / len(calls)
