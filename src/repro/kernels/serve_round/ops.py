"""Public wrapper: one engine serve round -> (max,+) affine-scan dispatch.

`core.engine._one_round` hands this wrapper the *sorted* per-item arrays of
one fixpoint round (items lexsorted by (channel, arrival, flat index), with
per-channel table gathers and seed gathers already done).  The wrapper

  1. runs the **static pre-pass**: the direction / DRAM row each item
     reacts to is the direction/row of the last *serving* (row-managed)
     item before it in its channel segment — a property of the ordering
     alone, resolved with exclusive running-max index gathers.  The
     turnaround gap and row hit/miss penalty then fold into per-item
     constants, and ``s = ser + row_extra`` is each item's total occupancy;
  2. builds each item's (max,+) affine map over the channel state
     ``v = (depart, down_until)`` — serving items advance ``depart`` (and
     ``down`` when they carry a retrain interval), link-down markers only
     raise ``down``, everything else is the identity — and folds the
     carried seed state into segment heads (which then kill the incoming
     state, making the scan unsegmented);
  3. rebases int64 picoseconds to int32 around the round's minimum arrival
     (seed clamps keep the rebase exact: a seed below the clamp floor is
     provably non-binding both before and after), dispatches the scan
     (Pallas kernel on TPU, interpret mode when forced, lax.scan oracle
     otherwise), and restores absolute times.

Returns the engine's masked per-item ``(start, depart, retrain_stall)``
triple in int64 picoseconds — non-serving items pass through at their
arrival with zero stall, exactly like the lax scan path — and ``ok``, a
bool scalar that is False when the round breaks the kernel's int32 span
contract (`kernel.SPAN_LIMIT`): an input over the limit before the int32
cast, or a composed value that reached it.  A round that is not ``ok``
carries no valid schedule; the engine resolves it on its int64 lax path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kernel import NEG, SPAN_LIMIT, serve_scan
from .ref import serve_scan_ref


@functools.partial(jax.jit, static_argnames=("impl",))
def serve_round(chan, serving, marker, arrive, direction, row, ser, turn,
                rhit, rmiss, retrain, sd_dep, sd_dir, sd_row, sd_down, *,
                impl: str = "auto"):
    """One sorted serve round.  All inputs (K,): ``chan`` int32 sorted with
    invalid items in a trailing dummy segment; ``serving``/``marker`` bool
    item classes; ``arrive``/``ser``/``turn``/``rhit``/``rmiss``/
    ``retrain``/``sd_dep``/``sd_down`` int64 ps; ``direction``/``sd_dir``
    int8; ``row``/``sd_row`` int32.  ``sd_*`` are the per-item gathered
    channel seed frontiers (cold: 0 / -1 / -2 / 0).  Returns int64
    ``(start, depart, stall)``."""
    k = chan.shape[0]
    idx = jnp.arange(k, dtype=jnp.int32)
    active = serving | marker
    dirn = direction.astype(jnp.int32)
    sdir = sd_dir.astype(jnp.int32)

    def prev_ix(mask):
        # index of the last item before me satisfying mask (-1 = none)
        inc = jax.lax.cummax(jnp.where(mask, idx, jnp.int32(-1)))
        return jnp.concatenate([jnp.full((1,), -1, jnp.int32), inc[:-1]])

    def in_seg(p):
        return (p >= 0) & (chan[jnp.maximum(p, 0)] == chan)

    p_act = prev_ix(active)
    p_srv = prev_ix(serving)
    p_row = prev_ix(serving & (row >= 0))
    head = active & ~in_seg(p_act)
    eff_dir = jnp.where(in_seg(p_srv), dirn[jnp.maximum(p_srv, 0)], sdir)
    eff_row = jnp.where(in_seg(p_row), row[jnp.maximum(p_row, 0)], sd_row)

    gap = jnp.where((eff_dir != -1) & (dirn != eff_dir), turn, jnp.int64(0))
    rx = jnp.where(row >= 0, jnp.where(row == eff_row, rhit, rmiss),
                   jnp.int64(0))
    s = ser + rx

    # int64 ps -> int32 rebased to the round's min arrival.  Seed clamps:
    # a depart seed below (base - turn) / a down seed below base can never
    # bind (every start is >= arrive >= base), so clamping preserves the
    # schedule bit-for-bit while keeping the rebase in range.
    base = jnp.min(arrive)
    rebased = (arrive - base, jnp.maximum(sd_dep, base - turn) - base,
               jnp.maximum(sd_down, base) - base, gap, s, retrain)
    in_hi = functools.reduce(jnp.maximum, [jnp.max(x) for x in rebased])
    arr, sdep, sdwn, gap32, s32, r32 = (x.astype(jnp.int32) for x in rebased)

    neg = jnp.full(k, NEG, jnp.int32)
    zero = jnp.zeros(k, jnp.int32)
    rp = jnp.where(r32 > 0, r32, neg)  # NEG = no retrain contribution

    # serving map: depart' = max(arr+s, depart+gap+s, down+s);
    #              down'   = max(down, depart' + retrain?)
    m00, m01, c0 = gap32 + s32, s32, arr + s32
    m10 = jnp.maximum(m00 + rp, neg)
    m11 = jnp.maximum(jnp.maximum(s32 + rp, zero), neg)
    c1 = jnp.maximum(c0 + rp, neg)
    # marker: depart' = depart; down' = max(down, arr + retrain)
    m00 = jnp.where(serving, m00, zero)
    m01 = jnp.where(serving, m01, neg)
    c0 = jnp.where(serving, c0, neg)
    m10 = jnp.where(serving, m10, neg)
    m11 = jnp.where(serving, m11, zero)
    c1 = jnp.where(serving, c1, jnp.where(marker, arr + r32, neg))
    # heads fold the seed state into c and kill the incoming state — this
    # is what de-segments the scan
    h0 = jnp.maximum(jnp.maximum(m00 + sdep, m01 + sdwn), c0)
    h1 = jnp.maximum(jnp.maximum(m10 + sdep, m11 + sdwn), c1)
    c0 = jnp.where(head, jnp.maximum(h0, NEG), c0)
    c1 = jnp.where(head, jnp.maximum(h1, NEG), c1)
    m00 = jnp.where(head, neg, m00)
    m01 = jnp.where(head, neg, m01)
    m10 = jnp.where(head, neg, m10)
    m11 = jnp.where(head, neg, m11)

    use = impl
    if use == "auto":
        use = "pallas" if jax.default_backend() == "tpu" else "ref"
    if use == "ref":
        # the sequential scan stores every state it reaches, so the first
        # over-limit depart is exact and shows in the outputs
        d32 = serve_scan_ref(m00, m01, m10, m11, c0, c1)
        hi = jnp.max(d32)
    else:
        d32, hi = serve_scan(m00, m01, m10, m11, c0, c1,
                             interpret=(use == "interpret"))
    ok = (in_hi <= SPAN_LIMIT) & (hi <= SPAN_LIMIT)
    d = d32.astype(jnp.int64) + base

    # stall = grant delay the down-until clock added on top of contention
    eff_dep = jnp.where(head, sd_dep,
                        jnp.concatenate([sd_dep[:1], d[:-1]]))
    start = d - s
    out_start = jnp.where(serving, start, arrive)
    out_depart = jnp.where(serving, d, arrive)
    out_stall = jnp.where(
        serving, start - jnp.maximum(arrive, eff_dep + gap), jnp.int64(0))
    return out_start, out_depart, out_stall, ok
