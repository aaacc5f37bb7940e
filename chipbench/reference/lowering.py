"""Reference lowering of a request table into per-hop tables.

Written from the configuration file alone (it imports nothing of the
program): the requests go in, and every request's hops come out in the
order it crosses the fabric, with each hop's channel, bytes, wire bytes,
serialization time, row and fixed latency.  The fabric's kind
(``reference/fabrics/<kind>.py``) gives each request's path.

A request climbs its path to the expander its line interleaves to, is
served there, and its response retraces the path.  Every link is full
duplex (one channel per direction), serializes whole flits
(``ceil(bytes / flit payload) * flit size`` wire bytes) at the link rate
capped by its credit loop, and adds its fixed latency, the flit FEC
latency and, where the next node is a switch, the switching time.  An
expander serves each access on the channel of its bank, byte-exact, with
DRAM row-buffer timing, then adds its fixed service time.  A read sends a
header out and its payload back; a write the reverse.

Rows keep the order of the request table, so that flat item indices and
FCFS tie breaks agree with the program's.
"""

from __future__ import annotations

import numpy as np

from chipbench import registry


def lower(cfg: dict, reqs: dict) -> dict:
    fab, link, ep, req = (cfg["fabric"], cfg["link"], cfg["endpoint"],
                          cfg["requester"])
    if float(link["ber"]) != 0.0 or link["duplex"] != "full":
        raise ValueError("reference models full-duplex links at BER 0")
    if req["interleave"] != "line" or req["header_model"] != "esf":
        raise ValueError("reference models line interleave, ESF packets")
    kind = registry.load("reference/fabrics", fab["kind"])
    n_mem = kind.n_memories(fab)
    addr = np.asarray(reqs["addr"], np.int64)
    write = np.asarray(reqs["is_write"], bool)
    mem = addr % n_mem
    line = addr // n_mem
    nodes, is_switch = kind.path(fab, reqs["host"], mem, reqs["route"])
    n_nodes = kind.n_nodes(fab)
    n, n_links = len(addr), len(nodes) - 1

    fsize, fpay = int(link["flit_size_B"]), int(link["flit_payload_B"])
    credit_cap = (int(link["rx_credits"]) * fsize * 1_000_000
                  // int(link["credit_rtt_ps"]))
    bw_link = min(int(link["bw_MBps"]), max(credit_cap, 1))
    hop_fixed = int(link["fixed_ps"]) + int(link["fec_ps"])
    sw = int(cfg["switching_ps"])
    header, payload = int(req["header_bytes"]), int(req["payload_bytes"])
    fwd_b = np.where(write, payload, header)
    bwd_b = np.where(write, header, payload)

    cols = []                  # (key, bytes, wire, ser, row, fixed, pay)

    def link_hop(u, v, nbytes, to_switch, pay):
        wire = -(-nbytes // fpay) * fsize
        cols.append((u * n_nodes + v, nbytes, wire,
                     wire * 1_000_000 // bw_link, np.full(n, -1),
                     np.full(n, hop_fixed + (sw if to_switch else 0)), pay))

    for k in range(n_links):
        link_hop(nodes[k], nodes[k + 1], fwd_b, is_switch[k + 1], write)
    banks, lpr = int(ep["banks"]), int(ep["lines_per_row"])
    cols.append((n_nodes * n_nodes + mem * banks + line % banks,
                 np.full(n, payload), np.full(n, payload),
                 np.full(n, payload * 1_000_000 // int(ep["bw_MBps"])),
                 (line // lpr) % (1 << 30), np.full(n, int(ep["fixed_ps"])),
                 np.ones(n, bool)))
    for k in range(n_links, 0, -1):
        link_hop(nodes[k], nodes[k - 1], bwd_b, is_switch[k - 1], ~write)

    keys, nb, wire, ser, row, fixed, pay = (np.stack(c, 1)
                                            for c in zip(*cols))
    uniq, chan = np.unique(keys, return_inverse=True)
    chan = chan.reshape(keys.shape)
    service = uniq >= n_nodes * n_nodes
    return {
        "channel": chan.astype(np.int64), "nbytes": nb.astype(np.int64),
        "wire": wire.astype(np.int64), "ser": ser.astype(np.int64),
        "row": row.astype(np.int64), "fixed": fixed.astype(np.int64),
        "is_payload": pay.astype(bool),
        "valid": np.ones(keys.shape, bool),
        "direction": np.zeros(keys.shape, np.int64),
        "issue": (np.asarray(reqs["issue_ps"], np.int64)
                  + int(req["overhead_ps"])),
        "n_channels": len(uniq),
        "turnaround": np.zeros(len(uniq), np.int64),
        "row_hit": np.where(service, int(ep["row_hit_extra_ps"]), 0),
        "row_miss": np.where(service, int(ep["row_miss_extra_ps"]), 0),
    }
