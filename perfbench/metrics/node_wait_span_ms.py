"""Dispatch (``core/coordinator.py::_dispatch``): per request, the start
of its node's ``node_slot`` span less the start of its ``request`` span,
mean -- the time behind the nodes served before it, from the program's
own spans (the twin of ``node_serial_wait_ms``)."""


def read(run):
    req, node = {}, {}
    for e in run.get("spans", []):
        tid = e.get("trace", "-")
        if tid == "-" or e.get("t1") is None:
            continue
        if e.get("name") == "request":
            req[tid] = e["t0"]
        elif e.get("name") == "node_slot":
            node[tid] = e["t0"]
    v = [t - req[k] for k, t in node.items() if k in req]
    return 1e3 * sum(v) / len(v) if v else None
