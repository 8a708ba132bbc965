"""Front door (``cluster/runtime.py`` slot loop): mean wait from a
request's due time until the ``run_slot`` call that carries it begins.
Source: the harness's own clock around its calls."""


def read(run):
    recs = [r for r in run["recs"] if r.dispatch >= 0]
    if not recs:
        return None
    return 1e3 * sum(r.dispatch - r.due for r in recs) / len(recs)
