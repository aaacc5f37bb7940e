"""The one traffic generator: builds a cell's requests from its traffic file
and the run's seed.

Every host runs an open-loop programme: it issues one request every
``issue_interval_ps``, starting at the chunk's start.  Its addresses walk
its own share of the rack's footprint (``footprint_lines`` lines from
``host * footprint_lines`` on) once, in an order drawn from the seed, so
that under line interleave every expander and every bank sees the same
number of requests from every host, whatever the seed.  A fixed share of
the requests (``1 - read_ratio``), placed by the seed, are writes.  Each
request carries a route choice for equal-cost multipath: a seeded order of
``0 .. n-1``, so a host spreads its requests evenly over any number of
alternatives that divides its request count.

So every seed gives the same sizes, counts, issue times and loads, in
another order.  Seeds may be any non-negative integer (no 32-bit limit):
they enter NumPy's `SeedSequence` together with the unit, the chunk and
the host.
"""

from __future__ import annotations

import numpy as np


def rng_for(seed: int, *salt: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), *(int(s) for s in salt)])


def chunk(traffic: dict, n_hosts: int, seed: int, unit: int,
          index: int = 0) -> dict:
    """Chunk ``index`` of unit ``unit``: ``interval_ps``, ``start_ps`` (the
    chunk follows the previous ones without a gap) and per host ``addr``
    (line addresses), ``is_write`` and ``route``."""
    n = int(traffic["requests_per_host"])
    share = int(traffic["footprint_lines"])
    if n > share:
        raise ValueError("a host walks its share once: requests_per_host "
                         "must not exceed footprint_lines")
    interval = int(traffic["issue_interval_ps"])
    n_writes = n - int(round(n * float(traffic["read_ratio"])))
    hosts = []
    for host in range(n_hosts):
        rng = rng_for(seed, unit, index, host)
        addr = host * share + rng.permutation(share)[:n].astype(np.int64)
        is_write = np.zeros(n, bool)
        is_write[rng.permutation(n)[:n_writes]] = True
        hosts.append({"addr": addr, "is_write": is_write,
                      "route": rng.permutation(n).astype(np.int64)})
    return {"interval_ps": interval, "start_ps": index * n * interval,
            "hosts": hosts}


def flat(ck: dict) -> dict:
    """A chunk as one request table in host-major order (the order the
    program lays its rows out in): ``host``, ``addr``, ``is_write``,
    ``route`` and ``issue_ps`` (before any requester overhead)."""
    hosts = ck["hosts"]
    n = len(hosts[0]["addr"])
    out = {k: np.concatenate([h[k] for h in hosts])
           for k in ("addr", "is_write", "route")}
    out["host"] = np.repeat(np.arange(len(hosts)), n)
    out["issue_ps"] = np.tile(ck["start_ps"]
                              + np.arange(n, dtype=np.int64)
                              * ck["interval_ps"], len(hosts))
    return out
