"""Reference telemetry: what the program's telemetry reports, worked out
from a reference schedule (`des.simulate`) and the hop tables it ran on.

Per request, the exact partition of its latency; per channel, the counters
of what crossed it and how long it waited; the observation span.  Plain
NumPy, importing nothing of the program.
"""

from __future__ import annotations

import numpy as np


def attribution(tab: dict, ref: dict) -> dict:
    """Per row: queue wait, wire serialization, row-buffer extra and fixed
    latency, which sum to ``complete - issue`` (no joins, no retraining)."""
    occ = tab["valid"] & (tab["ser"] > 0)
    wait = np.where(tab["valid"], ref["start"] - ref["arrive"][:, :-1], 0)
    return {
        "join_wait_ps": ref["arrive"][:, 0] - tab["issue"],
        "queue_wait_ps": wait.sum(1),
        "retrain_stall_ps": np.zeros(len(tab["issue"]), np.int64),
        "wire_ps": np.where(occ, tab["ser"], 0).sum(1),
        "row_extra_ps": np.where(occ, ref["extra"], 0).sum(1),
        "fixed_ps": np.where(tab["valid"], tab["fixed"], 0).sum(1),
        "total_ps": ref["complete"] - tab["issue"],
    }


def channel_counters(tab: dict, ref: dict, n_chan: int) -> dict:
    """Per channel (the tables' own channel ids): payload and wire bytes,
    busy time, queue wait, row-buffer extras and peak backlog (the most
    items queued at once, same-instant arrivals counted before grants)."""
    occ = (tab["valid"] & (tab["ser"] > 0)).ravel()
    c = tab["channel"].ravel()[occ]
    arr = ref["arrive"][:, :-1].ravel()[occ]
    st = ref["start"].ravel()[occ]
    dp = ref["depart"].ravel()[occ]

    def per_chan(x):
        out = np.zeros(n_chan, np.int64)
        np.add.at(out, c, np.asarray(x, np.int64))
        return out

    pay = np.where(tab["is_payload"].ravel()[occ], tab["nbytes"].ravel()[occ],
                   0)
    out = {
        "payload_bytes": per_chan(pay),
        "wire_bytes": per_chan(tab["wire"].ravel()[occ]),
        "busy_ps": per_chan(dp - st),
        "wait_ps": per_chan(st - arr),
        "row_extra_ps": per_chan(ref["extra"].ravel()[occ]),
    }
    # ±1 events: arrival +1, grant -1, ordered by (channel, time,
    # arrivals first); the running sum within a channel is its backlog
    ev_c = np.concatenate([c, c])
    ev_t = np.concatenate([arr, st])
    ev_y = np.concatenate([np.zeros(len(c), np.int64),
                           np.ones(len(c), np.int64)])
    order = np.lexsort((ev_y, ev_t, ev_c))
    delta = np.where(ev_y[order] == 0, 1, -1)
    run = np.cumsum(delta)
    peak = np.zeros(n_chan, np.int64)
    np.maximum.at(peak, ev_c[order], run)
    out["peak_backlog"] = peak
    return out


def span(tab: dict, ref: dict) -> int:
    """First issue to last completion, at least 1 ps."""
    return max(int(ref["complete"].max()) - int(ref["arrive"][:, 0].min()), 1)


# The latency histogram a stream reports: HDR bucketing over non-negative
# picoseconds.  Values under 2**SUB_BITS have a bucket each; above, every
# power-of-two octave splits into 2**SUB_BITS equal sub-buckets.
SUB_BITS = 5
SUB = 1 << SUB_BITS
BINS = (64 - SUB_BITS) * SUB
QUANTILES = (0.5, 0.99, 0.999)


def latency_bins(values) -> np.ndarray:
    v = np.maximum(np.asarray(values, np.int64), 0)
    e = np.array([max(int(x), 1).bit_length() - 1 for x in v.tolist()],
                 np.int64)                          # floor(log2(max(v, 1)))
    sub = (v >> np.maximum(e - SUB_BITS, 0)) - SUB
    return np.where(v < SUB, v, (e - SUB_BITS + 1) * SUB + sub)


def bin_value(b: int) -> int:
    """The value a bucket stands for: its lower edge plus half its width."""
    if b < SUB:
        return b
    shift = max(b // SUB, 1) - 1
    return ((SUB + b % SUB) << shift) + ((1 << shift) >> 1)


def latency_histogram(latency) -> dict:
    lat = np.asarray(latency, np.int64)
    return {"counts": np.bincount(latency_bins(lat), minlength=BINS),
            "n": len(lat), "min_ps": int(lat.min()), "max_ps": int(lat.max())}


def quantiles(hist: dict, qs=QUANTILES) -> np.ndarray:
    """Per ``q``: the value of the bucket that holds the ``ceil(q * n)``-th
    smallest latency, within the exact [min, max]; the exact min and max
    at the first and last rank."""
    n = hist["n"]
    cum = np.cumsum(hist["counts"])
    out = []
    for q in qs:
        rank = min(max(int(np.ceil(q * n)), 1), max(n, 1))
        if rank >= n:
            v = hist["max_ps"]
        elif rank <= 1:
            v = hist["min_ps"]
        else:
            b = min(int(np.searchsorted(cum, rank, side="left")), BINS - 1)
            v = min(max(bin_value(b), hist["min_ps"]), hist["max_ps"])
        out.append(v if n else 0)
    return np.asarray(out, np.int64)
