"""The configuration's fabric as the program builds it, and the program's
lowering of generated requests onto it (`devices.build_workload`).  The
topology comes from ``fabrics/<kind>.py``, named by the configuration's
``fabric.kind``."""

from __future__ import annotations

import numpy as np

from chipbench import registry


class Fabric:
    def __init__(self, cfg: dict):
        import repro.core as C

        if cfg["fabric"]["routing"] != "ecmp":
            raise ValueError(f"routing {cfg['fabric']['routing']!r} is not "
                             "modelled")
        self.C, self.cfg = C, cfg
        kind = registry.load("fabrics", cfg["fabric"]["kind"])
        self.graph = kind.topology(C, cfg).build()
        self.hosts = [int(r) for r in self.graph.topo.requesters()]
        self.mems = [int(m) for m in self.graph.topo.memories()]

    def lower(self, ck: dict):
        """One chunk of requests (`generator.chunk`) as the program lowers
        it: a `Workload`, rows in host-major order, each request routed by
        its route choice among the equal-cost paths."""
        C, req = self.C, self.cfg["requester"]
        specs = [C.RequesterSpec(
            node=h, n_requests=len(p["addr"]), targets=self.mems,
            trace_addr=p["addr"], trace_is_write=p["is_write"],
            issue_interval_ps=ck["interval_ps"], start_ps=ck["start_ps"],
            payload_bytes=int(req["payload_bytes"]))
            for h, p in zip(self.hosts, ck["hosts"])]
        return C.build_workload(
            self.graph, specs, header_bytes=int(req["header_bytes"]),
            header_model=req["header_model"], interleave=req["interleave"],
            route_choice=np.concatenate([p["route"] for p in ck["hosts"]]),
            requester_overhead_ps=int(req["overhead_ps"]))
