"""The path of a request across a spine-leaf rack, for the reference
lowering; written from the configuration alone.

Nodes: hosts ``0 .. n-1``, expanders ``n .. 2n-1``, then the host-side
leaves, the expander-side leaves (``n / per_leaf`` each) and the spines.
A request from host ``h`` to expander ``m`` climbs to h's leaf, crosses to
spine ``route mod n_spines`` (equal-cost multipath: the request's route
choice picks the spine), descends to m's leaf and reaches m.
"""

from __future__ import annotations

import numpy as np


def n_nodes(fab: dict) -> int:
    n, per_leaf = int(fab["n_pairs"]), int(fab["per_leaf"])
    return 2 * n + 2 * max(n // per_leaf, 1) + int(fab["n_spines"])


def n_memories(fab: dict) -> int:
    return int(fab["n_pairs"])


def path(fab: dict, host, mem, route) -> tuple[list, list]:
    """Per request, the nodes from host to expander ((N,) arrays, in
    order) and, per position, whether that node is a switch."""
    if fab["routing"] != "ecmp":
        raise ValueError(f"routing {fab['routing']!r} is not modelled")
    n, per_leaf = int(fab["n_pairs"]), int(fab["per_leaf"])
    side = max(n // per_leaf, 1)
    host, mem = np.asarray(host, np.int64), np.asarray(mem, np.int64)
    leaf_h = 2 * n + host // per_leaf
    leaf_m = 2 * n + side + mem // per_leaf
    spine = 2 * n + 2 * side + np.asarray(route, np.int64) % int(
        fab["n_spines"])
    return ([host, leaf_h, spine, leaf_m, n + mem],
            [False, True, True, True, False])
