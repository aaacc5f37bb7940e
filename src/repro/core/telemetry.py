"""Fabric telemetry: latency attribution, channel counters, windowed series,
streaming quantile sketches.

The engine answers *when* every transaction moved; the paper's §V studies
(and any calibration against hardware — Cohet, CXLRAMSim) need *why*: where
a request's latency went, which channel is the bottleneck, how tails evolve
over a run.  This module is the pure-observer instrumentation layer over
``(Hops, Channels, Schedule, issue_ps)``:

  * **Latency attribution** (`attribute_latency`) — an exact partition of
    every request's end-to-end latency into join-wait stall, FCFS queueing
    wait, retraining stall, wire serialization, DRAM row-buffer extras and
    fixed post-latency.  The partition is *conservative by construction*:
    the components of row ``i`` sum to ``complete[i] − issue[i]`` with zero
    residual, in exact int64 picoseconds (`conservation_residual` exposes
    the per-row check).  The retraining share is recovered by replaying one
    scan round from the resolved schedule (`engine.replay_round` — the
    schedule is a fixpoint of the round map, so the replay is exact and the
    schedule itself is never touched).

  * **Per-channel counters** (`channel_telemetry`) — logical payload bytes,
    actual wire bytes (flit quantization + sampled CRC-replay overhead),
    busy time, utilization, total queue wait, and peak backlog (the maximum
    number of simultaneously queued items, arrivals counted before the
    same-instant grant).

  * **Windowed series** (`windowed_series`) — time-bucketed busy fraction,
    completion throughput and mean in-flight over a fixed bin grid: the
    shape the ROADMAP's chunked streaming engine emits per window.

  * **Streaming quantile sketch** (`QuantileSketch`) — a fixed-shape
    HDR-style log-bucketed histogram (int64 ps, ~1.6 % relative error)
    with O(1)-state update/merge/query: the online p50/p99/p99.9
    accumulator that windowed simulation carries across chunks instead of
    materializing whole ``Schedule``s.

  * **SF protocol counters** (`sf_telemetry`) — hit rate, BISnp fan-out
    histogram (per-request snooped-owner popcounts) and InvBlk/writeback
    volume from the dense `SFEvents` log.

Everything here is a **pure function of already-computed results** — jit-
and vmap-safe (sweep telemetry vmaps alongside the sweep itself), and
provably non-perturbing: computing metrics cannot change a schedule, which
the test suite pins by re-simulating around a telemetry pass.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from .engine import (Channels, Hops, Schedule, channel_time_order,
                     replay_round, wire_ser_ps)
from .snoop_filter import SFEvents, owner_count

# ---------------------------------------------------------------------------
# Latency attribution
# ---------------------------------------------------------------------------


class LatencyAttribution(NamedTuple):
    """Exact per-request partition of ``complete − issue`` (int64 ps).

    ``join_wait + queue_wait + retrain_stall + wire + row_extra + fixed ==
    total`` holds per row with zero residual — the conservation invariant
    the property suite checks across flit-mode × reliability × join
    configs.  Components:

    join_wait_ps      fork/join release stall: the gap between a waiter
                      row's nominal issue and the max completion of its
                      contributor group (0 for non-waiters).
    queue_wait_ps     FCFS contention wait (turnaround gaps included),
                      *excluding* the retraining share below.
    retrain_stall_ps  grant delay attributable to link-down intervals
                      alone (stochastic reliability; 0 otherwise).
    wire_ps           wire serialization — flit quantization, expected
                      CRC-replay stretch and sampled replay bytes included.
    row_extra_ps      DRAM row-buffer hit/miss extras on service hops.
    fixed_ps          fixed post-hop latency (propagation, FEC, switching,
                      endpoint fixed service).
    total_ps          ``complete − issue``.
    """

    join_wait_ps: jnp.ndarray
    queue_wait_ps: jnp.ndarray
    retrain_stall_ps: jnp.ndarray
    wire_ps: jnp.ndarray
    row_extra_ps: jnp.ndarray
    fixed_ps: jnp.ndarray
    total_ps: jnp.ndarray


def attribute_latency(hops: Hops, channels: Channels, sched: Schedule,
                      issue_ps: jnp.ndarray) -> LatencyAttribution:
    """Attribute every request's latency to its mechanism (see
    `LatencyAttribution`).  Pure observer — reads the schedule, never
    recomputes it — and jit/vmap-safe (sweep telemetry vmaps over stacked
    ``Channels``/``Schedule`` axes like the sweep itself)."""
    c = channels.bw_MBps.shape[0]
    valid = hops.valid
    occupied = valid & (hops.nbytes > 0)
    clip = jnp.clip(hops.channel, 0, c - 1)

    hop_wait = jnp.where(valid, sched.start - sched.arrive[:, :-1], 0)
    hop_serv = jnp.where(valid, sched.depart - sched.start, 0)
    wire = jnp.where(
        occupied,
        wire_ser_ps(hops.nbytes, channels, clip,
                    extra_wire=hops.extra_wire_bytes),
        0,
    )
    if hops.retrain_after_ps is not None:
        _, _, stall = replay_round(hops, channels, sched)
        retrain = jnp.sum(jnp.where(valid, stall, 0), axis=1)
    else:
        retrain = jnp.zeros(valid.shape[0], jnp.int64)
    join_wait = sched.arrive[:, 0] - issue_ps
    return LatencyAttribution(
        join_wait_ps=join_wait,
        queue_wait_ps=jnp.sum(hop_wait, axis=1) - retrain,
        retrain_stall_ps=retrain,
        wire_ps=jnp.sum(wire, axis=1),
        row_extra_ps=jnp.sum(hop_serv - wire, axis=1),
        fixed_ps=jnp.sum(jnp.where(valid, hops.fixed_after_ps, 0), axis=1),
        total_ps=sched.complete - issue_ps,
    )


def conservation_residual(att: LatencyAttribution) -> jnp.ndarray:
    """Per-row conservation residual — exactly zero when the attribution
    partitions the latency (the hard invariant; nonzero means a schedule
    that is not a fixpoint of the round map, or a telemetry bug)."""
    parts = (att.join_wait_ps + att.queue_wait_ps + att.retrain_stall_ps
             + att.wire_ps + att.row_extra_ps + att.fixed_ps)
    return att.total_ps - parts


# ---------------------------------------------------------------------------
# Per-channel counters
# ---------------------------------------------------------------------------


class ChannelTelemetry(NamedTuple):
    """Per-channel counters over one schedule, shape (C,) unless noted.

    payload_bytes   logical payload bytes transmitted (header/DLLP bytes
                    excluded — `Hops.is_payload`).
    wire_bytes      actual wire bytes: flit-quantized (+ sampled CRC-replay
                    bytes under stochastic reliability).  The *expected*
                    replay model stretches time, not bytes — its overhead
                    shows in ``busy_ps``.
    busy_ps         total channel occupancy (serialization + row extras).
    wait_ps         total FCFS queue wait paid on the channel.
    utilization     ``busy_ps / window`` (float).
    peak_backlog    max simultaneously queued items (arrived, not yet
                    granted; same-instant arrivals counted before grants).
    window_ps       () — observation window (defaults to first arrival →
                    last completion).
    """

    payload_bytes: jnp.ndarray
    wire_bytes: jnp.ndarray
    busy_ps: jnp.ndarray
    wait_ps: jnp.ndarray
    utilization: jnp.ndarray
    peak_backlog: jnp.ndarray
    window_ps: jnp.ndarray


def hop_wire_bytes(hops: Hops, channels: Channels) -> jnp.ndarray:
    """Actual wire bytes of every hop: flit quantization plus the sampled
    per-hop CRC-replay bytes (`Hops.extra_wire_bytes`); byte-exact channels
    pass logical bytes through.  Zero on invalid / zero-byte hops."""
    c = channels.bw_MBps.shape[0]
    occupied = hops.valid & (hops.nbytes > 0)
    clip = jnp.clip(hops.channel, 0, c - 1)
    wire = hops.nbytes
    if channels.flit_size is not None:
        fsize = channels.flit_size[clip]
        fpay = jnp.maximum(channels.flit_payload[clip], 1)
        quant = ((hops.nbytes + fpay - 1) // fpay) * fsize
        if hops.extra_wire_bytes is not None:
            quant = quant + hops.extra_wire_bytes
        wire = jnp.where(fsize > 0, quant, wire)
    return jnp.where(occupied, wire, 0)


def channel_telemetry(hops: Hops, channels: Channels, sched: Schedule,
                      window: tuple | None = None) -> ChannelTelemetry:
    """Per-channel counters (see `ChannelTelemetry`).  Pure observer,
    jit/vmap-safe."""
    c = channels.bw_MBps.shape[0]
    n, h = hops.channel.shape
    k = n * h
    occupied = (hops.valid & (hops.nbytes > 0)).reshape(k)
    flat_c = jnp.where(occupied, hops.channel.reshape(k), c)

    busy_item = (sched.depart - sched.start).reshape(k)
    wait_item = (sched.start - sched.arrive[:, :h]).reshape(k)
    pay_item = jnp.where(hops.is_payload.reshape(k), hops.nbytes.reshape(k), 0)
    wire_item = hop_wire_bytes(hops, channels).reshape(k)

    def per_chan(x):
        return jnp.zeros(c + 1, jnp.int64).at[flat_c].add(
            jnp.where(occupied, x, 0))[:c]

    busy = per_chan(busy_item)
    wait = per_chan(wait_item)
    payload = per_chan(pay_item)
    wire = per_chan(wire_item)

    # peak backlog: ±1 events (arrival +1, grant −1) lexsorted by
    # (channel, time, arrivals-first — arrivals hold the lower event
    # indices); every channel's deltas sum to zero and segments are
    # channel-contiguous, so the global running sum IS the per-channel
    # backlog and a per-channel scatter-max reads the peak.
    times = jnp.concatenate([sched.arrive[:, :h].reshape(k),
                             sched.start.reshape(k)])
    chans2 = jnp.concatenate([flat_c, flat_c])
    delta = jnp.concatenate([jnp.where(occupied, 1, 0),
                             jnp.where(occupied, -1, 0)]).astype(jnp.int64)
    order = channel_time_order(chans2, times, c + 1)
    backlog = jnp.cumsum(delta[order])
    peak = jnp.zeros(c + 1, jnp.int64).at[chans2[order]].max(backlog)[:c]

    if window is None:
        t0 = jnp.min(sched.arrive[:, 0])
        t1 = jnp.max(sched.complete)
    else:
        t0, t1 = window
    span = jnp.maximum(t1 - t0, 1)
    return ChannelTelemetry(
        payload_bytes=payload, wire_bytes=wire, busy_ps=busy, wait_ps=wait,
        utilization=busy / span, peak_backlog=peak, window_ps=span,
    )


# ---------------------------------------------------------------------------
# Channel blame (aggregate bottleneck attribution)
# ---------------------------------------------------------------------------


class ChannelBlame(NamedTuple):
    """Aggregate per-channel blame: where the fleet's latency went.

    The per-request partition of `attribute_latency`, re-scattered onto the
    channel that charged each component — the jit/vmap-safe aggregate view
    of `core.critical_path`'s per-request walks (which add *which-event*
    structure on the host).  Conservation:

        Σ queue + Σ retrain + Σ wire + Σ row_extra + join + fixed == total

    exactly (int64 ps; `blame_conservation_residual`).

    queue_ps      (C,) FCFS contention wait per channel (turnaround gaps
                  included, retraining share excluded).
    retrain_ps    (C,) link-down stall per channel.
    wire_ps       (C,) serialization time per channel.
    row_extra_ps  (C,) row-buffer penalties per channel.
    join_ps       ()  fork/join release stall (channel-less).
    fixed_ps      ()  fixed post-hop latency (channel-less).
    total_ps      ()  Σ ``complete − issue``.
    """

    queue_ps: jnp.ndarray
    retrain_ps: jnp.ndarray
    wire_ps: jnp.ndarray
    row_extra_ps: jnp.ndarray
    join_ps: jnp.ndarray
    fixed_ps: jnp.ndarray
    total_ps: jnp.ndarray


def channel_blame(hops: Hops, channels: Channels, sched: Schedule,
                  issue_ps: jnp.ndarray) -> ChannelBlame:
    """Aggregate blame per channel (see `ChannelBlame`).  Pure observer,
    jit/vmap-safe; the retraining share comes from the same fixpoint replay
    as `attribute_latency`."""
    c = channels.bw_MBps.shape[0]
    n, h = hops.channel.shape
    k = n * h
    occupied = (hops.valid & (hops.nbytes > 0)).reshape(k)
    flat_c = jnp.where(occupied, hops.channel.reshape(k), c)
    clip = jnp.clip(hops.channel, 0, c - 1)

    def per_chan(x):
        return jnp.zeros(c + 1, jnp.int64).at[flat_c].add(
            jnp.where(occupied, x, 0))[:c]

    if hops.retrain_after_ps is not None:
        _, _, stall = replay_round(hops, channels, sched)
    else:
        stall = jnp.zeros((n, h), jnp.int64)
    wait = (sched.start - sched.arrive[:, :h]).reshape(k)
    busy = (sched.depart - sched.start).reshape(k)
    wire_t = wire_ser_ps(hops.nbytes, channels, clip,
                         extra_wire=hops.extra_wire_bytes).reshape(k)
    return ChannelBlame(
        queue_ps=per_chan(wait - stall.reshape(k)),
        retrain_ps=per_chan(stall.reshape(k)),
        wire_ps=per_chan(wire_t),
        row_extra_ps=per_chan(busy - wire_t),
        join_ps=jnp.sum(sched.arrive[:, 0] - issue_ps),
        fixed_ps=jnp.sum(jnp.where(hops.valid, hops.fixed_after_ps, 0)),
        total_ps=jnp.sum(sched.complete - issue_ps),
    )


def blame_conservation_residual(b: ChannelBlame) -> jnp.ndarray:
    """() int64 — zero iff the blame table partitions the total latency."""
    parts = (jnp.sum(b.queue_ps) + jnp.sum(b.retrain_ps) + jnp.sum(b.wire_ps)
             + jnp.sum(b.row_extra_ps) + b.join_ps + b.fixed_ps)
    return b.total_ps - parts


# ---------------------------------------------------------------------------
# Windowed series
# ---------------------------------------------------------------------------


class WindowedSeries(NamedTuple):
    """Fixed-grid time series over one schedule (all shapes (K,)).

    busy_ps        total channel occupancy inside each bin (all channels).
    busy_frac      ``busy_ps / (C · bin)`` — mean busy fraction (float).
    completions    requests completing inside each bin.
    inflight       time-averaged in-flight requests per bin (float).
    t0_ps, bin_ps  () — grid origin and bin width.
    """

    busy_ps: jnp.ndarray
    busy_frac: jnp.ndarray
    completions: jnp.ndarray
    inflight: jnp.ndarray
    t0_ps: jnp.ndarray
    bin_ps: jnp.ndarray


@functools.partial(jax.jit, static_argnames=("n_bins",))
def windowed_series(hops: Hops, channels: Channels, sched: Schedule,
                    issue_ps: jnp.ndarray, n_bins: int = 32,
                    window: tuple | None = None) -> WindowedSeries:
    """Bucket the schedule onto a fixed ``n_bins`` grid (see
    `WindowedSeries`).  Occupancy is split *exactly* across bins (partial
    overlap of a transmission with a bin counts its overlap), so the series
    sums to the channel totals.  ``n_bins`` is static (output shape)."""
    c = channels.bw_MBps.shape[0]
    n, h = hops.channel.shape
    if window is None:
        t0 = jnp.min(sched.arrive[:, 0])
        t1 = jnp.max(sched.complete)
    else:
        t0, t1 = window
    bin_ps = jnp.maximum((t1 - t0 + n_bins - 1) // n_bins, 1)
    edges = t0 + bin_ps * jnp.arange(n_bins + 1, dtype=jnp.int64)

    def coverage(lo, hi):
        """Σ overlap of the [lo, hi) intervals with each bin, exactly."""
        dur = jnp.maximum(hi - lo, 0).reshape(-1)
        lo = lo.reshape(-1)
        # f(t) = Σ clip(t − lo, 0, dur); per-bin coverage = f(e+1) − f(e)
        f = jnp.sum(jnp.clip(edges[:, None] - lo[None, :], 0,
                             dur[None, :]), axis=1)
        return f[1:] - f[:-1]

    occupied = hops.valid & (hops.nbytes > 0)
    busy = coverage(jnp.where(occupied, sched.start, 0),
                    jnp.where(occupied, sched.depart, 0))
    infl = coverage(issue_ps, sched.complete)

    comp = sched.complete
    in_range = (comp >= t0) & (comp <= t1)
    idx = jnp.clip((comp - t0) // bin_ps, 0, n_bins - 1)
    completions = jnp.zeros(n_bins, jnp.int64).at[idx].add(
        jnp.where(in_range, 1, 0))
    return WindowedSeries(
        busy_ps=busy,
        busy_frac=busy / (c * bin_ps),
        completions=completions,
        inflight=infl / bin_ps,
        t0_ps=t0, bin_ps=bin_ps,
    )


# ---------------------------------------------------------------------------
# Streaming quantile sketch (online p50/p99/p99.9)
# ---------------------------------------------------------------------------

SKETCH_SUB_BITS = 5                     # 32 sub-buckets per octave
_SKETCH_M = 1 << SKETCH_SUB_BITS
SKETCH_BINS = (64 - SKETCH_SUB_BITS) * _SKETCH_M
SKETCH_REL_ERROR = 1.0 / _SKETCH_M      # worst-case relative bucket width


class QuantileSketch(NamedTuple):
    """Streaming log-bucketed histogram over nonneg int64 picoseconds.

    HDR-histogram bucketing: values below 2^SKETCH_SUB_BITS are exact;
    above, each power-of-two octave splits into 2^SKETCH_SUB_BITS linear
    sub-buckets (≤ ~1.6 % relative error at the bucket midpoint).  State is
    one fixed-shape count vector plus exact min/max — O(1) memory, update /
    merge / quantile are all jit- and vmap-safe, and merging two sketches
    equals sketching the concatenation: the accumulator a chunked streaming
    engine carries across windows instead of materializing schedules.
    """

    counts: jnp.ndarray   # (SKETCH_BINS,) int64
    n: jnp.ndarray        # () int64
    min_ps: jnp.ndarray   # () int64 exact minimum (max int64 when empty)
    max_ps: jnp.ndarray   # () int64 exact maximum (0 when empty)


def sketch_new() -> QuantileSketch:
    return QuantileSketch(
        counts=jnp.zeros(SKETCH_BINS, jnp.int64),
        n=jnp.int64(0),
        min_ps=jnp.int64((1 << 62) - 1 + (1 << 62)),   # int64 max
        max_ps=jnp.int64(0),
    )


def sketch_bin(values: jnp.ndarray) -> jnp.ndarray:
    """Bucket index of each value (negative values clamp to 0)."""
    v = jnp.maximum(jnp.asarray(values, jnp.int64), 0)
    e = jnp.zeros_like(v)
    for s in (32, 16, 8, 4, 2, 1):      # e = floor(log2(max(v, 1)))
        e = e + jnp.where((v >> (e + s)) > 0, s, 0)
    small = v < _SKETCH_M
    sub = (v >> jnp.maximum(e - SKETCH_SUB_BITS, 0)) - _SKETCH_M
    return jnp.where(small, v,
                     (e - SKETCH_SUB_BITS + 1) * _SKETCH_M + sub)


def sketch_value(bins: jnp.ndarray) -> jnp.ndarray:
    """Representative (midpoint) value of each bucket index."""
    b = jnp.asarray(bins, jnp.int64)
    small = b < _SKETCH_M
    k = jnp.maximum(b // _SKETCH_M, 1)
    shift = k - 1                        # == octave − SKETCH_SUB_BITS
    lo = (_SKETCH_M + b % _SKETCH_M) << shift
    return jnp.where(small, b, lo + ((jnp.int64(1) << shift) >> 1))


def sketch_update(sk: QuantileSketch, values: jnp.ndarray,
                  mask: jnp.ndarray | None = None) -> QuantileSketch:
    """Fold a batch of values (optionally masked) into the sketch."""
    v = jnp.asarray(values, jnp.int64).reshape(-1)
    m = (jnp.ones(v.shape, bool) if mask is None
         else jnp.asarray(mask, bool).reshape(-1))
    idx = jnp.where(m, sketch_bin(v), 0)
    one = jnp.where(m, jnp.int64(1), 0)
    big = jnp.int64((1 << 62) - 1 + (1 << 62))
    return QuantileSketch(
        counts=sk.counts.at[idx].add(one),
        n=sk.n + jnp.sum(one),
        min_ps=jnp.minimum(sk.min_ps, jnp.min(jnp.where(m, v, big))),
        max_ps=jnp.maximum(sk.max_ps, jnp.max(jnp.where(m, v, 0))),
    )


def sketch_merge(a: QuantileSketch, b: QuantileSketch) -> QuantileSketch:
    return QuantileSketch(
        counts=a.counts + b.counts, n=a.n + b.n,
        min_ps=jnp.minimum(a.min_ps, b.min_ps),
        max_ps=jnp.maximum(a.max_ps, b.max_ps),
    )


def sketch_quantile(sk: QuantileSketch, q) -> jnp.ndarray:
    """Estimate the q-quantile (scalar or vector ``q`` in [0, 1]).

    Returns the representative value of the bucket holding the
    ``ceil(q·n)``-th smallest sample, clamped to the exact observed
    [min, max] — so p0/p100 are exact and every estimate is within one
    bucket (≤ ~1.6 % relative) of a true sample quantile.  0 when empty.
    """
    q = jnp.asarray(q, jnp.float64)
    cum = jnp.cumsum(sk.counts)
    rank = jnp.clip(jnp.ceil(q * sk.n).astype(jnp.int64), 1, jnp.maximum(sk.n, 1))
    idx = jnp.searchsorted(cum, rank, side="left")
    val = jnp.clip(sketch_value(jnp.minimum(idx, SKETCH_BINS - 1)),
                   sk.min_ps, sk.max_ps)
    # ranks 1 and n are the exact observed order statistics
    val = jnp.where(rank >= sk.n, sk.max_ps, val)
    val = jnp.where(rank <= 1, sk.min_ps, val)
    return jnp.where(sk.n > 0, val, 0)


def sketch_quantiles(sk: QuantileSketch,
                     qs=(0.5, 0.99, 0.999)) -> jnp.ndarray:
    """The tail vector the benches gate on — default (p50, p99, p99.9)."""
    return sketch_quantile(sk, jnp.asarray(qs))


# ---------------------------------------------------------------------------
# Streaming fold (windowed simulation accumulator)
# ---------------------------------------------------------------------------


class StreamTelemetry(NamedTuple):
    """Running accumulator for windowed simulation (`core.streaming`) —
    what the driver carries instead of materializing per-window
    ``Schedule``s.

    Per-window contributions are masked to *settled* items / *retired*
    rows, so boundary-spanning rows (which reappear in later windows as
    carried suffixes) fold exactly once and streaming totals equal the
    monolithic `channel_telemetry` counters bit-for-bit.  The latency
    sketch is `QuantileSketch` (mergeable, so merging per-window folds
    equals sketching the monolithic latencies).  Blame components
    (retrain / row-extra / join / fixed) fold from the same settled masks —
    a streamed `ChannelBlame` is derivable in `stream_telemetry_finalize`
    and equals the monolithic `channel_blame` bit-for-bit.  (Peak backlog
    needs a windowed event sort over the settled prefix; the streaming
    driver itself maintains it — `streaming.StreamState`.)

    payload_bytes/wire_bytes/busy_ps/wait_ps  (C,) int64 channel counters.
    retrain_ps    (C,) int64 link-down stall per channel (settled items).
    row_extra_ps  (C,) int64 row-buffer penalties per channel.
    join_ps       () int64 fork/join release stall (rows counted once, at
                  gate settlement).
    fixed_ps      () int64 fixed post-hop latency of settled items.
    sketch        latency `QuantileSketch` over retired requests.
    n_retired     () int64 requests retired so far.
    t0_ps/t1_ps   () int64 observation span (min issue / max completion of
                  retired requests; int64-max / 0 while empty).
    """

    sketch: QuantileSketch
    payload_bytes: jnp.ndarray
    wire_bytes: jnp.ndarray
    busy_ps: jnp.ndarray
    wait_ps: jnp.ndarray
    retrain_ps: jnp.ndarray
    row_extra_ps: jnp.ndarray
    join_ps: jnp.ndarray
    fixed_ps: jnp.ndarray
    n_retired: jnp.ndarray
    t0_ps: jnp.ndarray
    t1_ps: jnp.ndarray


def stream_telemetry_new(n_channels: int) -> StreamTelemetry:
    z = jnp.zeros(n_channels, jnp.int64)
    return StreamTelemetry(
        sketch=sketch_new(), payload_bytes=z, wire_bytes=z, busy_ps=z,
        wait_ps=z, retrain_ps=z, row_extra_ps=z,
        join_ps=jnp.int64(0), fixed_ps=jnp.int64(0), n_retired=jnp.int64(0),
        t0_ps=jnp.int64((1 << 62) - 1 + (1 << 62)), t1_ps=jnp.int64(0),
    )


@jax.jit
def stream_telemetry_fold(acc: StreamTelemetry, hops: Hops,
                          channels: Channels, sched: Schedule,
                          settled: jnp.ndarray, retired: jnp.ndarray,
                          latency_ps: jnp.ndarray,
                          stall_ps: jnp.ndarray,
                          gate_mask: jnp.ndarray,
                          gate_wait_ps: jnp.ndarray) -> StreamTelemetry:
    """Fold one resolved window into the accumulator.

    settled      (N, H) bool — items whose (start, depart) are final this
                 window (never again: the driver's settlement mask),
                 already AND-ed with validity.
    retired      (N,) bool — rows completing this window (padding excluded).
    latency_ps   (N,) int64 — ``complete − original issue`` per retired row
                 (the original issue survives window re-entry; junk where
                 ``retired`` is False).
    stall_ps     (N, H) int64 — per-item retraining stall from the window's
                 carry-seeded fixpoint replay (zeros without retrain
                 tables); a settled item's stall is final, so folding it
                 settled-masked reproduces the monolithic replay exactly.
    gate_mask    (N,) bool — rows whose hop-0 gate (join wait / issue)
                 became final this window; the driver guarantees each
                 global row is flagged exactly once across the stream.
    gate_wait_ps (N,) int64 — ``arrive[:, 0] − original issue`` per row
                 (junk where ``gate_mask`` is False).
    """
    c = channels.bw_MBps.shape[0]
    n, h = hops.channel.shape
    k = n * h
    occupied = (hops.valid & (hops.nbytes > 0) & settled).reshape(k)
    flat_c = jnp.where(occupied, hops.channel.reshape(k), c)
    clip = jnp.clip(hops.channel, 0, c - 1)

    def per_chan(x):
        return jnp.zeros(c + 1, jnp.int64).at[flat_c].add(
            jnp.where(occupied, x, 0))[:c]

    busy_item = (sched.depart - sched.start).reshape(k)
    wire_time = wire_ser_ps(hops.nbytes, channels, clip,
                            extra_wire=hops.extra_wire_bytes).reshape(k)
    busy = per_chan(busy_item)
    wait = per_chan((sched.start - sched.arrive[:, :h]).reshape(k))
    payload = per_chan(jnp.where(hops.is_payload.reshape(k),
                                 hops.nbytes.reshape(k), 0))
    wire = per_chan(hop_wire_bytes(hops, channels).reshape(k))
    retrain = per_chan(stall_ps.reshape(k))
    row_extra = per_chan(busy_item - wire_time)
    fixed = jnp.sum(jnp.where(settled, hops.fixed_after_ps, 0))
    join = jnp.sum(jnp.where(gate_mask, gate_wait_ps, 0))

    big = jnp.int64((1 << 62) - 1 + (1 << 62))
    iss = sched.complete - latency_ps
    return StreamTelemetry(
        sketch=sketch_update(acc.sketch, latency_ps, mask=retired),
        payload_bytes=acc.payload_bytes + payload,
        wire_bytes=acc.wire_bytes + wire,
        busy_ps=acc.busy_ps + busy,
        wait_ps=acc.wait_ps + wait,
        retrain_ps=acc.retrain_ps + retrain,
        row_extra_ps=acc.row_extra_ps + row_extra,
        join_ps=acc.join_ps + join,
        fixed_ps=acc.fixed_ps + fixed,
        n_retired=acc.n_retired + jnp.sum(retired.astype(jnp.int64)),
        t0_ps=jnp.minimum(acc.t0_ps, jnp.min(jnp.where(retired, iss, big))),
        t1_ps=jnp.maximum(acc.t1_ps,
                          jnp.max(jnp.where(retired, sched.complete, 0))),
    )


def stream_telemetry_finalize(acc: StreamTelemetry,
                              qs=(0.5, 0.99, 0.999)) -> dict:
    """Host-side summary of a finished (or in-progress) stream fold.

    The ``blame`` entry is the streamed `ChannelBlame` decomposition —
    queue wait is the folded wait minus the retraining share, wire time is
    folded busy minus row extras; with every window folded it equals the
    monolithic `channel_blame` bit-for-bit (property-tested).
    """
    span = max(int(acc.t1_ps) - int(acc.t0_ps), 1)
    import numpy as np

    wait = np.asarray(acc.wait_ps)
    busy = np.asarray(acc.busy_ps)
    retrain = np.asarray(acc.retrain_ps)
    row_extra = np.asarray(acc.row_extra_ps)
    return {
        "n_retired": int(acc.n_retired),
        "quantiles_ps": np.asarray(sketch_quantiles(acc.sketch, qs)),
        "payload_bytes": np.asarray(acc.payload_bytes),
        "wire_bytes": np.asarray(acc.wire_bytes),
        "busy_ps": busy,
        "wait_ps": wait,
        "utilization": busy / span,
        "span_ps": span,
        "blame": {
            "queue_ps": wait - retrain,
            "retrain_ps": retrain,
            "wire_ps": busy - row_extra,
            "row_extra_ps": row_extra,
            "join_ps": int(acc.join_ps),
            "fixed_ps": int(acc.fixed_ps),
        },
    }


# ---------------------------------------------------------------------------
# Snoop-filter protocol counters
# ---------------------------------------------------------------------------


class SFTelemetry(NamedTuple):
    """Protocol-decision counters from a dense `SFEvents` log.

    hit_rate      () float — local-cache hit fraction.
    fanout_hist   (R+1,) int64 — histogram of per-request snooped-owner
                  counts (index = popcount of ``bisnp_mask``; 0 = request
                  issued no snoops).
    bisnp_legs    () int64 — total BISnp legs (Σ owner popcounts).
    invblk_lines  () int64 — lines invalidated by InvBlk/conflict flows.
    wb_lines      () int64 — dirty lines written back.
    """

    hit_rate: jnp.ndarray
    fanout_hist: jnp.ndarray
    bisnp_legs: jnp.ndarray
    invblk_lines: jnp.ndarray
    wb_lines: jnp.ndarray


@functools.partial(jax.jit, static_argnames=("n_requesters",))
def sf_telemetry(events: SFEvents, n_requesters: int) -> SFTelemetry:
    owners = owner_count(events.bisnp_mask).astype(jnp.int64)
    hist = jnp.zeros(n_requesters + 1, jnp.int64).at[
        jnp.clip(owners, 0, n_requesters)].add(1)
    t = events.cache_hit.shape[0]
    return SFTelemetry(
        hit_rate=jnp.sum(events.cache_hit) / jnp.maximum(t, 1),
        fanout_hist=hist,
        bisnp_legs=jnp.sum(owners),
        invblk_lines=jnp.sum(events.inv_lines.astype(jnp.int64)),
        wb_lines=jnp.sum(events.wb_lines.astype(jnp.int64)),
    )


# ---------------------------------------------------------------------------
# Convenience aggregation
# ---------------------------------------------------------------------------


def fabric_metrics(hops: Hops, channels: Channels, sched: Schedule,
                   issue_ps: jnp.ndarray, n_bins: int = 32,
                   check: bool = True) -> dict:
    """One-call telemetry bundle: attribution + channel counters + windowed
    series + a latency sketch.  ``check=True`` (host-side, not jittable)
    raises if the conservation invariant fails."""
    att = attribute_latency(hops, channels, sched, issue_ps)
    if check:
        bad = int(jnp.max(jnp.abs(conservation_residual(att))))
        if bad != 0:
            raise AssertionError(
                f"latency attribution violates conservation by {bad} ps — "
                "the schedule is not a fixpoint of the round map (did it "
                "converge?) or telemetry has a bug")
    blame = channel_blame(hops, channels, sched, issue_ps)
    if check:
        bad = int(blame_conservation_residual(blame))
        if bad != 0:
            raise AssertionError(
                f"channel blame violates conservation by {bad} ps")
    sk = sketch_update(sketch_new(), att.total_ps)
    return {
        "attribution": att,
        "blame": blame,
        "channels": channel_telemetry(hops, channels, sched),
        "series": windowed_series(hops, channels, sched, issue_ps,
                                  n_bins=n_bins),
        "latency_sketch": sk,
        "latency_quantiles_ps": sketch_quantiles(sk),
        "rounds": sched.rounds,
        "converged": sched.converged,
    }
