"""Host microseconds per request in `devices.build_workload` (the layer
that lowers requester programs to hop tables), from the harness's own
spans around each call."""


def read(rec):
    n = rec["counters"].get("lowered_requests", 0)
    ns = [d for name, _, d in rec["spans"] if name == "build_workload"]
    if not n or not ns:
        return None
    return sum(ns) / 1e3 / n
