"""How a unit's outputs are held against the reference's: counts of the
entries that differ, so that an exact comparison has the limit 0."""

from __future__ import annotations

import numpy as np

CHANNEL_COUNTERS = ("payload_bytes", "wire_bytes", "busy_ps", "wait_ps",
                    "peak_backlog")


def mismatches(a, b) -> int:
    """Elements in which two arrays differ (all of them if shapes do)."""
    a, b = np.asarray(a), np.asarray(b)
    if a.shape != b.shape:
        return max(a.size, b.size, 1)
    return int(np.count_nonzero(a != b))


def channel_counters(got: dict, want: dict, ref_chan, prog_chan,
                     keys=CHANNEL_COUNTERS) -> int:
    """Mismatches of per-channel counters.  The reference numbers its own
    channels: they are lined up with the program's through the items that
    both hop tables place on them, which has to be one to one.  A program
    channel that no item uses has to count nothing."""
    ref_chan, prog_chan = np.asarray(ref_chan), np.asarray(prog_chan)
    if ref_chan.shape != prog_chan.shape:
        return max(ref_chan.size, 1)
    ref_ids, prog_ids = np.unique(
        np.stack([ref_chan.ravel(), prog_chan.ravel().astype(np.int64)]),
        axis=1)
    bad = (len(ref_ids) - len(np.unique(ref_ids))
           + len(prog_ids) - len(np.unique(prog_ids)))
    n_prog = len(np.asarray(got[keys[0]]))
    bad += int(np.count_nonzero(prog_ids >= n_prog))
    unused = np.ones(n_prog, bool)
    unused[prog_ids[prog_ids < n_prog]] = False
    for k in keys:
        g = np.asarray(got[k])
        bad += mismatches(g[np.minimum(prog_ids, n_prog - 1)],
                          np.asarray(want[k])[ref_ids])
        bad += int(np.count_nonzero(g[unused]))
    return bad
