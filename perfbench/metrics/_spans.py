"""Shared reading of the program's ``obs`` spans (one event per trace
of a batched span: an interval is counted once)."""


def intervals(run, name):
    seen = set()
    out = []
    for e in run.get("spans", []):
        if e.get("name") != name or e.get("t1") is None:
            continue
        key = (e["t0"], e["t1"])
        if key not in seen:
            seen.add(key)
            out.append(e["t1"] - e["t0"])
    return out


def per_request(run, name):
    return [e["t1"] - e["t0"] for e in run.get("spans", [])
            if e.get("name") == name and e.get("t1") is not None
            and e.get("trace", "-") != "-"]
