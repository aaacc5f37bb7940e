"""Serve-round (max,+) affine scan — the full ESF engine round (Pallas TPU).

One fixpoint round of the schedule engine — turnaround gaps, DRAM
row-buffer penalties, retraining down-until clocks, link-down markers and
streaming carry seeds included — reduces to an *unsegmented* associative
scan once the ops wrapper has done its static pre-pass:

  * the previous direction / DRAM row a sorted item reacts to depend only
    on the item ordering, never on the departure times, so the turnaround
    gap and row penalty fold into per-item constants;
  * what remains dynamic is the two-component channel state
    ``v = (depart, down_until)``, which every item transforms by a (max,+)
    affine map ``v' = M (x) v (+) c`` (serving item, link-down marker, or
    identity pass-through);
  * segment heads fold their channel's carried seed state into ``c`` and
    kill the incoming state (``M = NEG``), which removes segmentation from
    the scan entirely — maps compose across channel boundaries as plain
    (max,+) matrix products.

Layout: the K items are laid out row-major as ``(K / lanes, lanes)`` tiles
(``lanes = 128`` at the default block), and each grid step holds one
``(rows, lanes)`` block in VMEM.  The kernel runs a Hillis-Steele inclusive
composition scan in flat item order over the block — a shift by ``k < lanes``
is a lane rotate plus a sublane rotate for the wrapped lanes, a shift by a
multiple of ``lanes`` is a sublane rotate — and threads the absolute
``(depart, down)`` state across blocks in a lane-broadcast VMEM scratch
(sequential grid).

Times are int32: the ops wrapper rebases the engine's int64 picoseconds to
the round's minimum arrival.  Every finite value the scan composes must stay
under ``SPAN_LIMIT`` (2**29) so composed sums never overflow (compositions
add at most two rebased times before the ``NEG`` saturation clamp).  The
kernel enforces that contract itself: it returns, per block, the largest
value any composition step produced, and the first value to reach the limit
is computed from in-contract operands, so it is exact and cannot hide.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -(2 ** 30)  # tropical -inf
SPAN_LIMIT = (1 << 29) - 1  # largest finite value a round may compose
LANES = 128


def _serve_kernel(m00_ref, m01_ref, m10_ref, m11_ref, c0_ref, c1_ref,
                  d_ref, hi_ref, carry_d, carry_w, *, rows: int, lanes: int):
    # typed scalars: under jax_enable_x64 a bare python int would enter the
    # kernel as int64, which Mosaic cannot lower
    neg, zero = np.int32(NEG), np.int32(0)
    i = pl.program_id(0)

    @pl.when(i == zero)
    def _init():
        carry_d[...] = jnp.full_like(carry_d, neg)
        carry_w[...] = jnp.full_like(carry_w, neg)

    m00 = m00_ref[...]
    m01 = m01_ref[...]
    m10 = m10_ref[...]
    m11 = m11_ref[...]
    c0 = c0_ref[...]
    c1 = c1_ref[...]
    hi = jnp.maximum(jnp.maximum(jnp.maximum(m00, m01), jnp.maximum(m10, m11)),
                     jnp.maximum(c0, c1))

    shape = (rows, lanes)
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)

    def sh(x, fill, k):
        # x shifted by k items in flat (row-major) order; the first k items
        # take ``fill``
        if k < lanes:
            y = pltpu.roll(x, np.int32(k), 1)
            if rows > 1:
                y = jnp.where(lane >= np.int32(k), y, pltpu.roll(y, np.int32(1), 0))
            keep = (row > zero) | (lane >= np.int32(k))
        else:
            y = pltpu.roll(x, np.int32(k // lanes), 0)
            keep = row >= np.int32(k // lanes)
        return jnp.where(keep, y, fill)

    # Hillis-Steele inclusive scan of map composition; shifted-in slots are
    # the identity map (M = [[0, NEG], [NEG, 0]], c = NEG)
    k = 1
    while k < rows * lanes:
        p00, p01 = sh(m00, zero, k), sh(m01, neg, k)
        p10, p11 = sh(m10, neg, k), sh(m11, zero, k)
        q0, q1 = sh(c0, neg, k), sh(c1, neg, k)
        # (M, c) := (M, c) . (P, q) — P applied first:
        #   M' = M (x) P,  c' = M (x) q (+) c   (all saturated at NEG)
        n00 = jnp.maximum(jnp.maximum(m00 + p00, m01 + p10), neg)
        n01 = jnp.maximum(jnp.maximum(m00 + p01, m01 + p11), neg)
        n10 = jnp.maximum(jnp.maximum(m10 + p00, m11 + p10), neg)
        n11 = jnp.maximum(jnp.maximum(m10 + p01, m11 + p11), neg)
        nc0 = jnp.maximum(jnp.maximum(m00 + q0, m01 + q1), c0)
        nc1 = jnp.maximum(jnp.maximum(m10 + q0, m11 + q1), c1)
        m00, m01, m10, m11 = n00, n01, n10, n11
        c0 = jnp.maximum(nc0, neg)
        c1 = jnp.maximum(nc1, neg)
        hi = jnp.maximum(hi, jnp.maximum(
            jnp.maximum(jnp.maximum(m00, m01), jnp.maximum(m10, m11)),
            jnp.maximum(c0, c1)))
        k *= 2

    # apply the block-prefix maps to the inter-block absolute state
    # (a lane-broadcast (1, lanes) row, broadcast down the sublanes)
    d_in = carry_d[...]
    w_in = carry_w[...]
    d = jnp.maximum(jnp.maximum(m00 + d_in, m01 + w_in), c0)
    w = jnp.maximum(jnp.maximum(m10 + d_in, m11 + w_in), c1)
    d_ref[...] = d
    hi = jnp.maximum(hi, jnp.maximum(d, w))
    hi_ref[...] = jnp.broadcast_to(jnp.max(hi, axis=0, keepdims=True),
                                   hi_ref.shape)

    # the block's last item state, broadcast across the lanes
    last = (row == np.int32(rows - 1)) & (lane == np.int32(lanes - 1))

    def bcast_last(x):
        v = jnp.max(jnp.where(last, x, neg), axis=0, keepdims=True)
        return jnp.broadcast_to(jnp.max(v, axis=1, keepdims=True), (1, lanes))

    carry_d[...] = bcast_last(d)
    carry_w[...] = bcast_last(w)


@functools.partial(jax.jit, static_argnames=("blk", "interpret"))
def serve_scan(m00, m01, m10, m11, c0, c1, *, blk: int = 2048,
               interpret: bool = False):
    """Six (K,) int32 map components -> ``(depart, hi)``: the (K,) int32
    depart state per item, and the largest value any composition step
    produced (int32 scalar; the round is exact only if it is at most
    `SPAN_LIMIT`).  ``blk`` (a power of two) is the items per grid step."""
    k = m00.shape[0]
    lanes = min(blk, LANES)
    rows = blk // lanes
    pad = (-k) % blk
    if pad:
        def ext(x, fill):
            return jnp.concatenate([x, jnp.full((pad,), fill, jnp.int32)])
        m00, m11 = ext(m00, 0), ext(m11, 0)
        m01, m10 = ext(m01, NEG), ext(m10, NEG)
        c0, c1 = ext(c0, NEG), ext(c1, NEG)
    n = m00.shape[0]
    steps = n // blk
    tiles = [x.reshape(n // lanes, lanes) for x in (m00, m01, m10, m11, c0, c1)]
    spec = pl.BlockSpec((rows, lanes), lambda i: (i, np.int32(0)))
    # one (8, lanes) row of per-block maxima per grid step: 8 sublanes keeps
    # the output block tile-aligned
    d, hi = pl.pallas_call(
        functools.partial(_serve_kernel, rows=rows, lanes=lanes),
        grid=(steps,),
        in_specs=[spec] * 6,
        out_specs=[spec, pl.BlockSpec((8, lanes),
                                      lambda i: (i, np.int32(0)))],
        out_shape=[jax.ShapeDtypeStruct((n // lanes, lanes), jnp.int32),
                   jax.ShapeDtypeStruct((steps * 8, lanes), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((1, lanes), jnp.int32),
                        pltpu.VMEM((1, lanes), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(*tiles)
    return d.reshape(n)[:k], jnp.max(hi)
