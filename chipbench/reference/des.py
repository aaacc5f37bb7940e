"""Reference simulator: the plain semantics of a fabric run.

Written from the semantics the configurations state, taking plain NumPy
arrays, so that it imports nothing of the program and no later change to
the program can move it.

Semantics, per channel:
  * items are served in order of (arrival time, flat item index
    ``row * H + hop``), first come first served;
  * a half-duplex channel frees ``turnaround`` later when the served item's
    direction differs from the previous item's;
  * a row-managed channel adds ``row_hit`` when the item's DRAM row equals
    the previous row accessed on that channel, else ``row_miss`` (a cold
    channel misses);
  * an item departs at ``start + ser + row extra`` and arrives at its next
    hop ``fixed`` later; zero-byte hops pass straight through (adding their
    fixed latency), padded hops pass through unchanged.

Every item on a channel is served in key order ``(arrival, flat index)``,
and an item's next hop arrives strictly later than the item itself (or, for
a pass-through, with a larger index).  So the whole schedule is resolved by
taking the items in global key order from one heap: when an item is taken,
every item of smaller key on its channel has been served, and the
channel's state is that of the previous item in FCFS order.

``clock`` rounds every time the simulation computes.  The exact reference
keeps Python integers (picoseconds, unbounded); a control passes a rounding
to a narrower type (`float32_clock`), which is how a lower-precision clock
would compute the same schedule.  ``reverse_ties`` breaks the FCFS
guarantee instead: items that arrive at the same time are served in
descending flat index, as an ordering by arrival alone may serve them.
"""

from __future__ import annotations

import heapq

import numpy as np


def exact_clock(t: int) -> int:
    return t


def float32_clock(t: int) -> int:
    """``t`` as float32 holds it (24-bit mantissa), back as an integer."""
    return int(np.float32(t))


# The controls: the reference put in the program's place, computed on the
# nearest lower precision of the configuration's int64 picosecond clock
# that can round (float32), or with the FCFS tie-break guarantee broken.
CONTROLS = {"float32_clock": {"clock": float32_clock},
            "reverse_ties": {"reverse_ties": True}}


def simulate(chan, ser, direction, row, fixed, valid, issue,
             turnaround, row_hit, row_miss, clock=exact_clock,
             reverse_ties: bool = False) -> dict:
    """Resolve the FCFS schedule.

    Per item (N, H): ``chan`` channel id, ``ser`` serialization time in ps
    (0 = zero-byte pass-through), ``direction`` 0/1, ``row`` DRAM row
    (-1 = not row-managed), ``fixed`` ps after the hop, ``valid``.  Per row
    (N,): ``issue`` ps.  Per channel: ``turnaround``, ``row_hit``,
    ``row_miss`` ps.  Returns int64 ``arrive`` (N, H+1), ``start``,
    ``depart``, ``extra`` (N, H; the row-buffer extra of each hop) and
    ``complete`` (N,).
    """
    chan = np.asarray(chan)
    n, h = chan.shape
    c_l = chan.ravel().tolist()
    s_l = np.asarray(ser).ravel().tolist()
    d_l = np.asarray(direction).ravel().tolist()
    r_l = np.asarray(row).ravel().tolist()
    f_l = np.asarray(fixed).ravel().tolist()
    v_l = np.asarray(valid).ravel().tolist()
    turn = np.asarray(turnaround).tolist()
    rhit = np.asarray(row_hit).tolist()
    rmiss = np.asarray(row_miss).tolist()
    n_ch = max(len(turn), max(c_l, default=-1) + 1)
    free_t = [0] * n_ch
    last_dir = [-1] * n_ch
    last_row = [-2] * n_ch

    h1 = h + 1
    arrive = [0] * (n * h1)
    start = [0] * (n * h)
    depart = [0] * (n * h)
    extra = [0] * (n * h)
    tie = -1 if reverse_ties else 1
    heap: list[tuple[int, int]] = []

    def reach(i: int, t: int):
        """Item ``i`` arrives at ``t``: pass it through the hops that
        serve nothing; the heap key of the first that does, if any."""
        p, hop = divmod(i, h)
        while hop < h:
            arrive[p * h1 + hop] = t
            if v_l[i] and s_l[i] != 0:
                return (t, tie * i)
            start[i] = depart[i] = t
            if v_l[i]:
                t = clock(t + f_l[i])
            i += 1
            hop += 1
        arrive[p * h1 + h] = t
        return None

    for p, t in enumerate(np.asarray(issue).tolist()):
        key = reach(p * h, clock(int(t)))
        if key is not None:
            heap.append(key)
    heapq.heapify(heap)
    pop, push = heapq.heappop, heapq.heappush

    while heap:
        t, k = pop(heap)
        i = k * tie
        c = c_l[i]
        d = d_l[i]
        ld = last_dir[c]
        gap = turn[c] if (ld != -1 and d != ld) else 0
        st = clock(max(t, clock(free_t[c] + gap)))
        r = r_l[i]
        ex = 0
        if r >= 0:
            ex = rhit[c] if r == last_row[c] else rmiss[c]
            last_row[c] = r
        dp = clock(st + s_l[i] + ex)
        start[i] = st
        depart[i] = dp
        extra[i] = ex
        free_t[c] = dp
        last_dir[c] = d
        if (i + 1) % h:
            key = reach(i + 1, clock(dp + f_l[i]))
            if key is not None:
                push(heap, key)
        else:
            arrive[(i // h) * h1 + h] = clock(dp + f_l[i])

    arrive = np.asarray(arrive, np.int64).reshape(n, h1)
    return {"arrive": arrive,
            "start": np.asarray(start, np.int64).reshape(n, h),
            "depart": np.asarray(depart, np.int64).reshape(n, h),
            "extra": np.asarray(extra, np.int64).reshape(n, h),
            "complete": arrive[:, h]}
