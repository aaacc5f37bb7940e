"""Tensorized transaction schedule engine (ESF device layer, TPU-native).

The C++ ESF resolves link/endpoint contention with an event loop.  An event
loop is data-dependent control flow — the worst shape for an accelerator — so
this port reformulates transaction-level simulation as a fixpoint of dense
tensor ops, which jits and (crucially) ``vmap``s over whole sweeps of system
configurations:

  * Every transaction is a row of hop records ``(channel, bytes, direction,
    row, fixed_after)`` (request hops, an endpoint-service hop, response hops).
  * FCFS contention per channel is a *segmented tropical scan*: with items
    sorted by (channel, arrival, tiebreak), within a channel segment

        start_i  = max(arrive_i, depart_{i-1} [+ turnaround if direction flip])
        depart_i = start_i + serialize_i [+ row-buffer penalty]

  * Arrival times satisfy ``arrive[p, h+1] = depart[p, h] + fixed_after[p, h]``.
    We initialize arrivals with the contention-free schedule (a lower bound)
    and iterate sort→scan→propagate until the integer fixpoint is reached.
    Delays only ever grow toward the true FCFS schedule, whose exactness is
    checked against a pure-Python event-driven oracle (`core.ref_des`) in the
    test suite.

All times are int64 **picoseconds** and all sizes int64 bytes, so schedules are
exact and tie-breaking (by flat item index = packet-major order) is
deterministic and identical to the oracle.

The per-channel carried state (busy-until, last direction, last DRAM row,
and — under stochastic link reliability — retraining down-until) is what
lets one mechanism model full-duplex PCIe links, half-duplex buses with
turnaround, switch ports, banked DRAM endpoints, and link-down stalls
uniformly — ESF's "decoupling design" (§III-A) expressed as data instead of
classes.
"""

from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

PS_PER_S = 1_000_000_000_000


def ser_ps(nbytes, bw_MBps):
    """Exact integer serialization time: bytes / (MB/s) in picoseconds.

    bytes * 1e6 // MBps  ==  bytes * 1e12 // (MBps * 1e6) exactly, with an
    int64 overflow headroom of ~9 TB per packet instead of ~9 MB."""
    return (nbytes * 1_000_000) // bw_MBps


def wire_ser_ps(nbytes, ch: "Channels", chan_clipped, extra_wire=None):
    """Serialization time of ``nbytes`` logical bytes on their channels,
    honouring the link-layer flit tables (`core.link_layer`):

      * flit channels transmit whole flits — ceil(bytes/payload) * size wire
        bytes — and stretch by the expected Go-Back-N CRC-replay overhead
        ``(1 + replay_ppm/1e6)``, floored to exact integer picoseconds;
      * byte-exact channels (flit_size 0, or seed-layout Channels with no
        flit tables at all) keep the seed formula bit-for-bit;
      * ``extra_wire`` (stochastic reliability, `Hops.extra_wire_bytes`)
        adds the build-time-sampled CRC-replay wire bytes of each item —
        zero off flit channels, and mutually exclusive with a nonzero
        ``replay_ppm`` on the same channel by the lowering contract.
    """
    bw = ch.bw_MBps[chan_clipped]
    if ch.flit_size is None:
        return ser_ps(nbytes, bw)
    fsize = ch.flit_size[chan_clipped]
    flit = fsize > 0
    fpay = jnp.maximum(ch.flit_payload[chan_clipped], 1)
    wire = ((nbytes + fpay - 1) // fpay) * fsize
    if extra_wire is not None:
        wire = wire + extra_wire
    # one divide by the bandwidth for both kinds of channel: a variable
    # int64 divide is the costliest op the TPU compiler builds here
    ser = ser_ps(jnp.where(flit, wire, nbytes), bw)
    if ch.replay_ppm is not None:
        ppm = ch.replay_ppm[chan_clipped]
        # floor(ser * (1e6 + ppm) / 1e6), decomposed so the product never
        # exceeds int64 even with ppm at the MAX_REPLAY_PPM clamp (1e9):
        # identical to the oracle's arbitrary-precision formula for any
        # ser below ~9.2e15 ps
        scale = 1_000_000 + ppm
        q, r = ser // 1_000_000, ser % 1_000_000
        ser = jnp.where(flit, q * scale + (r * scale) // 1_000_000, ser)
    return ser


class Channels(NamedTuple):
    """Static per-channel tables (from `FabricGraph`).

    The three optional flit tables are the link-layer lowering contract of
    `core.link_layer`: a channel with ``flit_size > 0`` serializes whole
    flits (``ceil(bytes / flit_payload) * flit_size`` wire bytes) and pays
    the expected CRC-replay overhead ``replay_ppm`` (parts-per-million of
    extra transmissions under Go-Back-N retry).  ``None`` — the seed layout —
    or all-zero tables reproduce byte-exact serialization bit-for-bit.
    Because they are plain per-channel arrays, BER / flit-mode sweeps
    ``vmap`` over them without rebuilding hop tables.
    """

    bw_MBps: jnp.ndarray        # (C,) int64
    turnaround_ps: jnp.ndarray  # (C,) int64, half-duplex direction-flip cost
    row_hit_ps: jnp.ndarray     # (C,) int64 extra when row matches
    row_miss_ps: jnp.ndarray    # (C,) int64 extra when row differs / cold
    flit_size: jnp.ndarray | None = None     # (C,) int64, 0 = byte-exact
    flit_payload: jnp.ndarray | None = None  # (C,) int64
    replay_ppm: jnp.ndarray | None = None    # (C,) int64


class Hops(NamedTuple):
    """Per-transaction hop table, shape (N, H); padded hops have valid=False.

    The two optional (N, H) tables carry the stochastic link-reliability
    samples (`core.link_layer.sample_hop_tables`, seeded at build time):
    ``extra_wire_bytes`` — sampled Go-Back-N replay wire bytes added to the
    hop's serialization; ``retrain_after_ps`` — link-down interval the hop's
    channel enters when the hop departs (retraining stall; the channel
    grants nothing until it ends).  ``None`` — the deterministic
    expected-value layout — keeps the scan structurally identical to PR 1.

    The three optional (N,) tables are the **fork/join primitive**: a row
    whose ``join_wait >= 0`` does not issue at its nominal issue time but at
    ``max(issue, max completion of every row whose join_id names the same
    group)`` — max-of-arrivals join semantics (a DCOH collecting the *last*
    BIRsp of a concurrent BISnp fan-out, CXL 3.x BI flows).  ``join_id``
    marks a row as a contributor to a group; ``join_arity`` (meaningful on
    waiter rows) is the contract: the number of contributors the group must
    receive, which the event-driven oracle uses as its release count and
    validates against the table.  Group ids live in the row index space —
    ``0 <= id < N`` — because the engine resolves group maxes with an
    N-sized scatter (the oracle validates the bound).  Groups must form a
    DAG through rows
    (a row may both wait on one group and contribute to another — the
    coherence lowering chains request -> snoop fan-out -> demand leg this
    way); a cycle deadlocks the oracle (detected and raised) and never
    converges in the engine.  ``None`` — no joins — keeps the fixpoint
    structurally identical to the chain-only engine.
    """

    channel: jnp.ndarray      # (N, H) int32
    nbytes: jnp.ndarray       # (N, H) int64 serialized bytes on this hop
    direction: jnp.ndarray    # (N, H) int8  0/1 for half-duplex channels
    row: jnp.ndarray          # (N, H) int32 DRAM row id, -1 = not row-managed
    fixed_after_ps: jnp.ndarray  # (N, H) int64 latency after transmission
    is_payload: jnp.ndarray   # (N, H) bool — payload (vs header) bytes
    valid: jnp.ndarray        # (N, H) bool
    extra_wire_bytes: jnp.ndarray | None = None   # (N, H) int64
    retrain_after_ps: jnp.ndarray | None = None   # (N, H) int64
    join_id: jnp.ndarray | None = None     # (N,) int32 group fed, -1 = none
    join_wait: jnp.ndarray | None = None   # (N,) int32 group gating issue, -1
    join_arity: jnp.ndarray | None = None  # (N,) int32 contributors expected


class Schedule(NamedTuple):
    """Resolved schedule + the unified convergence diagnostics every
    simulation result type in `repro.core` exposes under the same names:
    ``rounds`` / ``converged`` / ``residual_ps`` (see also `CoupledResult`
    and `streaming.StreamResult`)."""

    arrive: jnp.ndarray    # (N, H+1) arrival per hop; [:, H] = completion
    start: jnp.ndarray     # (N, H) channel grant time
    depart: jnp.ndarray    # (N, H) transmission end
    complete: jnp.ndarray  # (N,)
    rounds: jnp.ndarray    # () iterations used
    converged: jnp.ndarray  # () bool
    residual_ps: jnp.ndarray | None = None  # () last round's max |Δarrive|


class StreamCarry(NamedTuple):
    """Per-channel frontier state carried across streaming windows
    (`core.streaming`).

    The FCFS service order on a channel equals the global key order
    ``(arrival, flat index)``, so once every item that can still arrive has
    a later key, the channel's history collapses to the state after its
    last settled item — exactly the scan carry `_one_round` threads through
    a segment.  A window seeded with this state schedules its items
    bit-identically to the monolithic run (the `ref_des` oracle mirrors the
    same seeds via its ``free_at`` map).

    depart_ps      (C,) int64 — busy-until of the last settled serving item
                   (0 = channel never served).
    last_dir       (C,) int8 — its direction (-1 = none: no turnaround due).
    last_row       (C,) int32 — last settled DRAM row (-2 = cold).
    down_until_ps  (C,) int64 — max retraining down interval contributed by
                   settled items/markers (0 = link up).
    join_seed_ps   (N,) int64 or None — carried fork/join group maxes in the
                   *window's* group-id space: entry ``g`` is the max
                   completion of the group's already-retired contributors
                   (`_join_gate` folds it into the scatter-max).  When
                   non-None the window's `Hops` must carry join tables.
    """

    depart_ps: jnp.ndarray
    last_dir: jnp.ndarray
    last_row: jnp.ndarray
    down_until_ps: jnp.ndarray
    join_seed_ps: jnp.ndarray | None = None


def empty_carry(n_channels: int, n_rows: int | None = None) -> StreamCarry:
    """A cold carry: seeding `simulate` with it is bit-identical to no carry
    (fresh channels, no down intervals, no retired join contributors)."""
    return StreamCarry(
        depart_ps=jnp.zeros(n_channels, jnp.int64),
        last_dir=jnp.full(n_channels, -1, jnp.int8),
        last_row=jnp.full(n_channels, -2, jnp.int32),
        down_until_ps=jnp.zeros(n_channels, jnp.int64),
        join_seed_ps=(None if n_rows is None
                      else jnp.zeros(n_rows, jnp.int64)),
    )


_CHECK_MODES = ("off", "static", "oracle")


@dataclasses.dataclass(frozen=True)
class SimOptions:
    """One options surface for every simulation entry point.

    `simulate`, `simulate_auto`, `coherence_traffic.simulate_coupled` and
    `streaming.simulate_stream` all accept an ``options=SimOptions(...)``
    argument; each consumes the subset of fields that applies to it and
    ignores the rest, so one options object can be threaded through a whole
    pipeline.  The historical per-function kwargs (``max_rounds=``,
    ``check=True/False``, ``damping=``, ``static_check=``,
    ``oracle_fallback=``) remain as deprecated shims that warn and fold
    into an equivalent ``SimOptions``.

    max_rounds  fixpoint round budget; 0 (default) = the computed
                join-depth-aware `round_bound` — provably sufficient, so
                explicit budgets are only for experiments that *want* a
                truncated fixpoint.
    check       "off"    — no verification, no host sync (the returned
                           schedule may be unconverged);
                "static" — run the fabric-IR verifier (`core.verify`)
                           before tracing, then behave as "oracle";
                "oracle" — fall back to the event-driven `ref_des` oracle
                           when the fixpoint reports non-convergence
                           (replaces the old ``check=True`` bool /
                           ``check="static"`` string overload).
    damping     damped Picard iteration in `simulate_coupled`'s outer
                coherence fixpoint (ignored by the other entry points).
    use_kernel  run the inner serve round through the Pallas kernel
                (`kernels.serve_round`): ``True`` = backend auto-dispatch
                (TPU kernel, lax elsewhere), or an explicit impl string
                ``"pallas"`` / ``"interpret"`` / ``"ref"``.
    """

    max_rounds: int = 0
    check: str = "oracle"
    damping: bool = False
    use_kernel: bool | str = False

    def __post_init__(self):
        if self.check not in _CHECK_MODES:
            raise ValueError(
                f"SimOptions.check must be one of {_CHECK_MODES}, "
                f"got {self.check!r}")

    @property
    def kernel_impl(self) -> str:
        """`_one_round` dispatch string for ``use_kernel``."""
        if self.use_kernel is False:
            return "scan"
        if self.use_kernel is True:
            return "auto"
        return self.use_kernel


def _legacy_check(val) -> str:
    """Map the historical ``check=`` overload onto `SimOptions.check`."""
    if val == "static":
        return "static"
    if isinstance(val, str) and val in _CHECK_MODES:
        return val
    return "oracle" if val else "off"


def _merge_options(fn: str, options, **legacy) -> SimOptions:
    """Resolve ``options`` plus deprecated per-call kwargs (``None`` =
    not passed) into one `SimOptions`, warning per legacy kwarg."""
    if isinstance(options, int):
        # historical positional max_rounds
        legacy = {**legacy, "max_rounds": options}
        options = None
    opts = options if options is not None else SimOptions()
    if not isinstance(opts, SimOptions):
        raise TypeError(f"{fn}: options must be a SimOptions, "
                        f"got {type(opts).__name__}")
    updates = {}
    for name, val in legacy.items():
        if val is None:
            continue
        if name == "check":
            val = _legacy_check(val)
        warnings.warn(
            f"{fn}({name}=...) is deprecated; pass "
            f"options=SimOptions({name}={val!r})",
            DeprecationWarning, stacklevel=3)
        updates[name] = val
    return dataclasses.replace(opts, **updates) if updates else opts


def round_bound(hops: Hops) -> int:
    """Join-depth-aware fixpoint round budget for a lowered `Hops` table —
    ``(join_depth + 1) * (3*H + 8)`` (see `verify.round_bound` for the
    derivation).  Host-side: called on concrete tables at build time or by
    the `simulate` wrapper.  Inside a ``jit``/``vmap`` trace the join
    tables are abstract, so the bound degrades to the chain-only term —
    join-heavy sweeps should compute the bound on the concrete tables and
    pass ``SimOptions(max_rounds=round_bound(hops))`` explicitly.
    """
    from . import verify  # host-side helper module, no jax imports

    h = int(hops.channel.shape[-1])
    jid, jw = hops.join_id, hops.join_wait
    if jid is None or jw is None:
        return verify.round_bound(h)
    if isinstance(jid, jax.core.Tracer) or isinstance(jw, jax.core.Tracer):
        return verify.round_bound(h)
    jid, jw = np.asarray(jid), np.asarray(jw)
    if jid.ndim == 1:
        return verify.round_bound(h, jid, jw)
    # stacked tables (host-side sweep layouts): the max over members
    return max(verify.round_bound(h, j, w)
               for j, w in zip(jid.reshape(-1, jid.shape[-1]),
                               jw.reshape(-1, jw.shape[-1])))


@jax.jit
def hop_ser_ps(hops: Hops, ch: Channels):
    """(N, H) serialization time of every hop on its channel — a static
    property of the item, computed once per simulation.  Jitted on its own:
    its int64 divides are the costliest code the TPU compiler builds for
    the engine, and every fixpoint program of one workload (each serve
    implementation, each stream window of one shape) shares this one."""
    clip = jnp.minimum(hops.channel, ch.bw_MBps.shape[0] - 1)
    return wire_ser_ps(hops.nbytes, ch, clip, extra_wire=hops.extra_wire_bytes)


def _one_round(hops: Hops, ch: Channels, issue_ps, arrive, ser,
               with_stalls=False, carry: StreamCarry | None = None,
               impl: str = "scan"):
    """One sort→segmented-scan→propagate pass.  arrive: (N, H+1); ser:
    (N, H) `hop_ser_ps`.

    ``with_stalls=True`` (telemetry replay, `core.telemetry`) additionally
    returns the per-item retraining-stall share of the queueing wait —
    ``start − max(arrive, contention floor)``, the part of the grant delay
    attributable to the channel's link-down interval alone.  The default
    path is byte-identical to the plain round (the extra outputs exist only
    under the flag, which is resolved at trace time).

    ``carry`` (streaming windows, `core.streaming`) seeds every segment
    head with the channel's carried frontier instead of a cold channel:
    the head's previous-item state comes from a per-channel gather, the
    turnaround gap applies only when a direction is actually carried
    (``last_dir != -1``), and down-until state is threaded even without
    per-hop retrain tables.  Resolved at trace time — ``carry=None``
    compiles the exact historical scan.
    """
    n, h = hops.channel.shape
    k = n * h
    flat_arrive = arrive[:, :h].reshape(k)
    flat_chan = hops.channel.reshape(k)
    flat_valid = hops.valid.reshape(k)
    # the round's steps carry named scopes (`core.spans`): ``round.order``,
    # ``round.gather``, ``round.serve`` and ``round.scatter`` in the op_name
    # metadata of the compiled program, so a profile splits a round by step
    with jax.named_scope("round.order"):
        # push invalid items to a dummy tail segment so they never contend
        sort_chan = jnp.where(flat_valid, flat_chan,
                              jnp.int32(ch.bw_MBps.shape[0]))
        order = channel_time_order(sort_chan, flat_arrive,
                                   ch.bw_MBps.shape[0] + 1)

    # stochastic retraining stalls extend the carry with per-channel
    # down-until state — resolved at trace time so the deterministic layout
    # compiles to the exact PR-1 scan
    has_retrain = hops.retrain_after_ps is not None
    has_carry = carry is not None
    with jax.named_scope("round.gather"):
        chan_clipped = jnp.minimum(flat_chan[order], ch.bw_MBps.shape[0] - 1)
        s_chan = flat_chan[order]
        s_valid = flat_valid[order]
        s_arrive = flat_arrive[order]
        s_dir = hops.direction.reshape(k)[order]
        s_row = hops.row.reshape(k)[order]
        s_bytes = hops.nbytes.reshape(k)[order]
        s_ser = ser.reshape(k)[order]
        s_turn = ch.turnaround_ps[chan_clipped]
        s_rowhit = ch.row_hit_ps[chan_clipped]
        s_rowmiss = ch.row_miss_ps[chan_clipped]
        xs = (s_chan, s_valid, s_arrive, s_dir, s_row, s_ser, s_turn,
              s_rowhit, s_rowmiss, s_bytes)
        if has_retrain:
            xs = xs + (hops.retrain_after_ps.reshape(k)[order],)
        if has_carry:
            seed_ix = jnp.clip(s_chan, 0, ch.bw_MBps.shape[0] - 1)
            xs = xs + (carry.depart_ps[seed_ix], carry.last_dir[seed_ix],
                       carry.last_row[seed_ix], carry.down_until_ps[seed_ix])

    def scan_fn(state, x):
        if has_retrain or has_carry:
            prev_chan, prev_depart, prev_dir, prev_row, prev_down = state
        else:
            prev_chan, prev_depart, prev_dir, prev_row = state
        chan, valid, arr, drn, row, ser, turn, rhit, rmiss, nbytes = x[:10]
        ix = 10
        if has_retrain:
            retrain = x[ix]
            ix += 1
        if has_carry:
            sd_dep, sd_dir, sd_row, sd_down = x[ix:ix + 4]
        # zero-byte packets ride a side channel (e.g. DRAM command path):
        # they pass through instantly and do not occupy or turn the bus.
        # Exception: a zero-byte hop carrying retrain_after_ps is a
        # *link-down marker* (`link_layer.insert_retrain_markers`) — it
        # still occupies nothing but pushes its channel's down_until to
        # (arrival + retrain), mirroring a full-duplex partner's stall.
        if has_retrain:
            marker = valid & (nbytes == 0) & (retrain > 0)
        valid = valid & (nbytes > 0)
        same = chan == prev_chan
        if has_carry:
            # segment heads resume from the carried per-channel frontier
            # (gathered seeds) instead of a cold channel; the turnaround
            # gap requires an actually-carried direction
            eff_dep = jnp.where(same, prev_depart, sd_dep)
            eff_dir = jnp.where(same, prev_dir, sd_dir)
            eff_row = jnp.where(same, prev_row, sd_row)
            eff_down = jnp.where(same, prev_down, sd_down)
            gap = jnp.where((eff_dir != jnp.int8(-1)) & (drn != eff_dir),
                            turn, 0)
            start = jnp.maximum(arr, jnp.maximum(eff_dep + gap, eff_down))
            if with_stalls:
                # grant time on a healthy link: the carried/segment down
                # interval is the only extra term, so the stall is whatever
                # it adds on top of contention + turnaround
                stall = jnp.where(valid,
                                  start - jnp.maximum(arr, eff_dep + gap), 0)
            row_extra = jnp.where(
                row >= 0, jnp.where(row == eff_row, rhit, rmiss), 0)
        else:
            gap = jnp.where(same & (drn != prev_dir), turn, 0)
            floor = prev_depart + gap
            if has_retrain:
                # a retraining link grants nothing until down_until passes;
                # the state is per channel, i.e. per scan segment — reset
                # on entry
                seg_down = jnp.where(same, prev_down, jnp.int64(0))
                if with_stalls:
                    # grant time the item would have seen on a healthy
                    # link — the retrain stall is whatever the down
                    # interval adds on top
                    nodown = jnp.where(same, jnp.maximum(arr, floor), arr)
                floor = jnp.maximum(floor, seg_down)
            start = jnp.where(same, jnp.maximum(arr, floor), arr)
            if with_stalls:
                stall = (jnp.where(valid, start - nodown, 0) if has_retrain
                         else jnp.zeros_like(start))
            row_managed = row >= 0
            row_extra = jnp.where(
                row_managed,
                jnp.where(same & (row == prev_row), rhit, rmiss),
                0,
            )
        depart = start + ser + row_extra
        start = jnp.where(valid, start, arr)
        depart = jnp.where(valid, depart, arr)
        ys = (start, depart) + ((stall,) if with_stalls else ())
        if has_carry:
            # markers keep the seeded frontier alive (the carried channel
            # history must survive a marker opening a segment) and only
            # raise down_until; serving items advance it as usual
            mk = marker if has_retrain else jnp.zeros_like(valid)
            upd = valid | mk
            new_carry = (
                jnp.where(upd, chan, prev_chan),
                jnp.where(valid, depart, jnp.where(mk, eff_dep, prev_depart)),
                jnp.where(valid, drn, jnp.where(mk, eff_dir, prev_dir)),
                jnp.where(valid & (row >= 0), row,
                          jnp.where(upd, eff_row, prev_row)),
            )
            contrib = (jnp.where(retrain > 0, depart + retrain, jnp.int64(0))
                       if has_retrain else jnp.int64(0))
            new_down = jnp.maximum(eff_down, contrib)
            new_carry = new_carry + (jnp.where(upd, new_down, prev_down),)
            return new_carry, ys
        if not has_retrain:
            new_carry = (
                jnp.where(valid, chan, prev_chan),
                jnp.where(valid, depart, prev_depart),
                jnp.where(valid, drn, prev_dir),
                jnp.where(valid & (row >= 0), row, prev_row),
            )
            return new_carry, ys
        # a marker opening a segment initializes the channel state to "no
        # previous item" (depart 0, row -2) so the next real hop sees a
        # fresh channel plus the marker's down interval; mid-segment it
        # leaves everything but down_until untouched.  Markers are only
        # emitted for full-duplex pairs (turnaround 0, not row-managed),
        # so the stored direction never creates a spurious turnaround.
        head = marker & ~same
        new_carry = (
            jnp.where(valid | marker, chan, prev_chan),
            jnp.where(valid, depart, jnp.where(head, jnp.int64(0),
                                               prev_depart)),
            jnp.where(valid, drn, jnp.where(head, drn, prev_dir)),
            jnp.where(valid & (row >= 0), row,
                      jnp.where(head, jnp.int32(-2), prev_row)),
        )
        new_down = jnp.maximum(
            seg_down, jnp.where(retrain > 0, depart + retrain,
                                jnp.int64(0)))
        new_carry = new_carry + (
            jnp.where(valid | marker, new_down, prev_down),)
        return new_carry, ys

    def lax_round():
        init = (jnp.int32(-1), jnp.int64(0), jnp.int8(-1), jnp.int32(-2))
        if has_retrain or has_carry:
            init = init + (jnp.int64(0),)
        return jax.lax.scan(scan_fn, init, xs)[1]

    if impl == "scan":
        with jax.named_scope("round.serve"):
            out = lax_round()
    else:
        # Pallas serve-round kernel (`kernels.serve_round`): one code path
        # for every layout — deterministic/no-carry configs ride the carry
        # semantics with cold seeds, bit-identical by the empty-carry
        # equivalence the streaming suite property-tests.  A round that
        # breaks the kernel's int32 span contract is resolved on the int64
        # lax path instead, so no over-span round returns a kernel schedule.
        from ..kernels.serve_round.ops import serve_round

        with jax.named_scope("round.gather"):
            s_retrain = (hops.retrain_after_ps.reshape(k)[order]
                         if has_retrain else jnp.zeros(k, jnp.int64))
            if has_carry:
                seed_ix = jnp.clip(s_chan, 0, ch.bw_MBps.shape[0] - 1)
                sd = (carry.depart_ps[seed_ix], carry.last_dir[seed_ix],
                      carry.last_row[seed_ix], carry.down_until_ps[seed_ix])
            else:
                sd = (jnp.zeros(k, jnp.int64), jnp.full(k, -1, jnp.int8),
                      jnp.full(k, -2, jnp.int32), jnp.zeros(k, jnp.int64))
        with jax.named_scope("round.serve"):
            serving = s_valid & (s_bytes > 0)
            marker = s_valid & (s_bytes == 0) & (s_retrain > 0)
            *kout, ok = serve_round(
                s_chan, serving, marker, s_arrive, s_dir, s_row, s_ser,
                s_turn, s_rowhit, s_rowmiss, s_retrain, *sd, impl=impl)
            kout = tuple(kout[:3 if with_stalls else 2])
            out = jax.lax.cond(ok, lambda: kout, lax_round)
    with jax.named_scope("round.scatter"):
        return _scatter_round(hops, issue_ps, order, *out)


def channel_time_order(chan, time, n_chan: int):
    """Permutation sorting items by ``(chan, time, flat index)``; ``chan``
    takes values in ``[0, n_chan)``.

    Two unstable sorts with unique keys instead of two stable argsorts: the
    time pass takes the flat index as its second key, and the channel pass
    sorts the packed key ``chan * K + time position`` (int32 wherever it
    fits).  The order is the same; the TPU compiler builds a stable int64
    argsort of a deployment-size round several times more slowly.
    """
    k = time.shape[0]
    idx = jnp.arange(k, dtype=jnp.int32)
    by_time = jax.lax.sort((time, idx), num_keys=2, is_stable=False)[1]
    kdt = jnp.int32 if n_chan * k < 2 ** 31 else jnp.int64
    key = chan[by_time].astype(kdt) * k + idx.astype(kdt)
    return jax.lax.sort((key, by_time), num_keys=1, is_stable=False)[1]


def _unpermute(order, *xs):
    """Put arrays in sorted order back into flat item order: for the
    permutation ``order``, each result is ``zeros(k).at[order].set(x)``,
    bit for bit.  One unstable sort keyed on ``order`` (its keys are unique)
    carries every array at once; the TPU compiler cannot know that
    ``order`` is a permutation, and builds each scatter through it for
    colliding indices: on a TPU v5e, two such scatters of a 589,824-item
    round take about forty times as long as this one sort."""
    return jax.lax.sort((order,) + xs, num_keys=1, is_stable=False)[1:]


def _scatter_round(hops: Hops, issue_ps, order, *sorted_out):
    """Un-permute the sorted per-item grants ``(start, depart[, stall])``
    back to (N, H) by one sort on ``order`` (`_unpermute`), and propagate
    exact arrivals (padded hops pass the previous arrival through)."""
    n, h = hops.channel.shape
    start, depart, *stall = (x.reshape(n, h)
                             for x in _unpermute(order, *sorted_out))

    cols = [issue_ps]
    for j in range(h):
        cols.append(jnp.where(
            hops.valid[:, j], depart[:, j] + hops.fixed_after_ps[:, j], cols[-1]
        ))
    new_arrive = jnp.stack(cols, axis=1)
    return (new_arrive, start, depart, *stall)


def _join_gate(hops: Hops, issue_ps, arrive, join_seed=None):
    """Fork/join issue gating: the effective issue time of a waiter row is
    ``max(issue, max completion of its group's contributors)``.

    Group maxes are resolved as a scatter-max over the current iterate's
    completion column — a per-group running max folded between FCFS scan
    rounds rather than inside one (the scan runs in (channel, arrival)
    order, where a running max over completions is not computable; between
    rounds it is exact at the fixpoint, and join delays only ever grow, so
    the contention-free initialization stays a valid lower bound).

    ``join_seed`` ((N,) int64, streaming windows) folds in the carried
    completions of contributors that already retired in earlier windows —
    `StreamCarry.join_seed_ps`, indexed in the window's group-id space.
    """
    n, h = hops.channel.shape
    comp = arrive[:, h]
    contrib = hops.join_id >= 0
    gmax = jnp.zeros((n,), jnp.int64).at[
        jnp.where(contrib, hops.join_id, 0)
    ].max(jnp.where(contrib, comp, jnp.int64(0)))
    if join_seed is not None:
        gmax = jnp.maximum(gmax, join_seed)
    wait = hops.join_wait >= 0
    gate = gmax[jnp.clip(hops.join_wait, 0, n - 1)]
    return jnp.where(wait, jnp.maximum(issue_ps, gate), issue_ps)


def simulate(hops: Hops, channels: Channels, issue_ps: jnp.ndarray,
             options: SimOptions | None = None, *,
             carry: StreamCarry | None = None,
             max_rounds: int | None = None) -> Schedule:
    """Resolve the exact FCFS schedule of all transactions.

    ``options`` (`SimOptions`) selects the round budget and the serve-round
    implementation; ``options=None`` is ``SimOptions()``.  The default
    budget (``max_rounds=0``) is the computed join-depth-aware
    `round_bound` — sufficient for every verifier-legal lowering, so
    convergence is provable rather than hand-tuned; truncated-fixpoint
    experiments pass an explicit ``SimOptions(max_rounds=...)``.
    Convergence is reported in ``Schedule.converged`` and the last round's
    max arrival delta in ``Schedule.residual_ps`` (0 at the fixpoint).

    ``carry`` (`StreamCarry`, built by `core.streaming`) seeds the window
    with the per-channel frontier / down-until state and retired join-group
    maxes of everything already settled — the streaming windowed mode.
    ``carry=None`` (the default) traces the exact historical program, so
    non-streaming entry points stay bit- and jit-cache-identical.

    ``max_rounds=`` as a direct kwarg is deprecated (folds into
    ``options`` with a `DeprecationWarning`).

    The budget is resolved host-side and passed to the jitted fixpoint as
    a *traced* operand, so sweeping budgets (or growing the computed bound
    across lowerings of one shape) never recompiles; the
    ``lax.while_loop`` early-exits on the first unchanged round, so a
    generous bound costs nothing at runtime.
    """
    opts = _merge_options("simulate", options, max_rounds=max_rounds)
    budget = opts.max_rounds if opts.max_rounds > 0 else round_bound(hops)
    return _simulate_fixpoint(hops, channels, issue_ps,
                              hop_ser_ps(hops, channels), jnp.int64(budget),
                              carry, opts.kernel_impl)


@functools.partial(jax.jit, static_argnames=("impl",))
def _simulate_fixpoint(hops: Hops, channels: Channels, issue_ps, ser0,
                       rounds, carry: StreamCarry | None,
                       impl: str) -> Schedule:
    n, h = hops.channel.shape
    has_join = hops.join_id is not None
    join_seed = carry.join_seed_ps if carry is not None else None

    # contention-free lower bound initialization (sampled replay stretch
    # included: it delays the item even uncontended; retraining stalls and
    # join gates only ever delay items, so they keep this a valid lower
    # bound)
    step = jnp.where(hops.valid, ser0 + hops.fixed_after_ps, 0)
    arrive0 = issue_ps[:, None] + jnp.concatenate(
        [jnp.zeros((n, 1), jnp.int64), jnp.cumsum(step, axis=1)], axis=1
    )

    def cond(state):
        i, arrive, _, _, resid = state
        return (i < rounds) & (resid != 0)

    def body(state):
        i, arrive, _, _, _ = state
        eff_issue = (_join_gate(hops, issue_ps, arrive, join_seed)
                     if has_join else issue_ps)
        new_arrive, start, depart = _one_round(hops, channels, eff_issue,
                                               arrive, ser0, carry=carry,
                                               impl=impl)
        resid = jnp.max(jnp.abs(new_arrive - arrive))
        return i + 1, new_arrive, start, depart, resid

    z = jnp.zeros((n, h), jnp.int64)
    i, arrive, start, depart, resid = jax.lax.while_loop(
        cond, body, (jnp.int64(0), arrive0, z, z, jnp.int64(-1))
    )
    return Schedule(
        arrive=arrive, start=start, depart=depart,
        complete=arrive[:, h], rounds=i, converged=resid == 0,
        residual_ps=jnp.maximum(resid, 0),
    )


def replay_round(hops: Hops, channels: Channels, sched: Schedule,
                 carry: StreamCarry | None = None):
    """Re-run one FCFS round from a resolved schedule (telemetry replay).

    The exact schedule is a fixed point of the round map, so replaying one
    sort→scan pass from ``sched.arrive`` reproduces ``start``/``depart``
    bit-for-bit — and on the way extracts the per-hop **retraining-stall**
    share of each grant delay (the only latency component the final
    schedule arrays alone cannot separate from ordinary queueing).  Returns
    ``(start, depart, retrain_stall)``, each ``(N, H)``; the stall table is
    all zeros for deterministic-reliability layouts.  Pure observer: the
    schedule is an input, never recomputed.

    ``carry`` replays a streaming window from its seeded frontier
    (`core.streaming` folds per-window blame with it); a window's schedule
    is a fixpoint of the *seeded* round map, so the same argument applies.
    """
    _, start, depart, stall = _one_round(
        hops, channels, sched.arrive[:, 0], sched.arrive,
        hop_ser_ps(hops, channels), with_stalls=True, carry=carry)
    return start, depart, stall


# ---------------------------------------------------------------------------
# Post-schedule metrics (paper Figs. 10–12, 16, 17)
# ---------------------------------------------------------------------------

def simulate_auto(hops: Hops, channels: Channels, issue_ps: jnp.ndarray,
                  options: SimOptions | None = None, *,
                  carry: StreamCarry | None = None,
                  max_rounds: int | None = None,
                  check: bool | str | None = None) -> tuple[Schedule, bool]:
    """Exact schedule with oracle fallback.

    The fixpoint converges within the computed `round_bound` for
    feed-forward traffic (the common case: topology sweeps, collective
    traces, join-gated coherence flows).  Tight feedback loops — requests
    and responses interleaving on one shared half-duplex channel — can
    converge only a few queue positions per round; rather than burn
    unbounded rounds, fall back to the event-driven oracle
    (`core.ref_des`), which is exact by construction and fast at bench
    sizes.  Returns (schedule, used_oracle).

    ``SimOptions.check`` selects the verification mode:

    "off"     skip the ``bool(sched.converged)`` readback — the only
              device→host sync on this path.  Callers that already pull
              the schedule to the host (the streaming driver does, every
              window, for carry extraction) use it to keep the window
              pipeline transfer-free and run their own fallback; the
              returned schedule may then be unconverged.
    "oracle"  (default) fall back to the oracle on non-convergence.
    "static"  additionally run the fabric-IR verifier (`core.verify`)
              over the lowered triple *before* tracing anything and raise
              `verify.VerifyError` on any contract violation — the
              belt-and-braces mode for tables a third-party lowering
              produced.  An explicit round budget below the computed
              bound is a ``join.depth`` finding.

    ``carry`` threads streaming window state into both the fixpoint and
    the oracle fallback.  ``max_rounds=`` / ``check=`` direct kwargs are
    deprecated shims (``check=True`` ≙ "oracle", ``check=False`` ≙ "off").
    """
    opts = _merge_options("simulate_auto", options, max_rounds=max_rounds,
                          check=check)
    if opts.check == "static":
        from . import verify  # local import: host-side checker only

        verify.assert_valid(hops, channels, issue_ps, carry=carry,
                            max_rounds=opts.max_rounds or None)
    sched = simulate(hops, channels, issue_ps, opts, carry=carry)
    if opts.check == "off":
        return sched, False
    if bool(sched.converged):
        return sched, False
    from . import ref_des  # local import: oracle pulls in heapq only

    ref = ref_des.simulate_ref(hops, channels, issue_ps, carry=carry)
    return Schedule(
        arrive=jnp.asarray(ref["arrive"]),
        start=jnp.asarray(ref["start"]),
        depart=jnp.asarray(ref["depart"]),
        complete=jnp.asarray(ref["complete"]),
        rounds=sched.rounds,
        converged=jnp.bool_(True),
        residual_ps=jnp.int64(0),
    ), True


def channel_stats(hops: Hops, sched: Schedule, channels: Channels,
                  window: tuple[jnp.ndarray, jnp.ndarray] | None = None) -> dict:
    """Per-channel busy time, payload time and queue waits.

    bus utility (Fig. 17)        = busy / window, averaged over directions
    transmission efficiency      = payload transmit time / busy time

    Payload time counts *logical* payload bytes while busy time is actual
    wire occupancy, so on flit-mode channels (`core.link_layer`) efficiency
    directly measures the flit packing fraction: a saturated stream of
    fully packed 256 B flits reads 236/256, shrinking as CRC replays grow.
    """
    c = channels.bw_MBps.shape[0]
    busy_item = jnp.where(hops.valid, sched.depart - sched.start, 0)
    wait_item = jnp.where(hops.valid, sched.start - sched.arrive[:, :-1], 0)
    ser_item = ser_ps(hops.nbytes, channels.bw_MBps[jnp.minimum(hops.channel, c - 1)])
    pay_item = jnp.where(hops.valid & hops.is_payload, ser_item, 0)
    flat_c = jnp.where(hops.valid, hops.channel, c).reshape(-1)
    busy = jnp.zeros(c + 1, jnp.int64).at[flat_c].add(busy_item.reshape(-1))[:c]
    payload = jnp.zeros(c + 1, jnp.int64).at[flat_c].add(pay_item.reshape(-1))[:c]
    wait = jnp.zeros(c + 1, jnp.int64).at[flat_c].add(wait_item.reshape(-1))[:c]
    if window is None:
        t0 = jnp.min(sched.arrive[:, 0])
        t1 = jnp.max(sched.complete)
    else:
        t0, t1 = window
    span = jnp.maximum(t1 - t0, 1)
    return {
        "busy_ps": busy,
        "payload_ps": payload,
        "wait_ps": wait,
        "utility": busy / span,
        "efficiency": payload / jnp.maximum(busy, 1),
        "window_ps": span,
    }


def request_stats(hops: Hops, sched: Schedule, issue_ps: jnp.ndarray,
                  payload_bytes: jnp.ndarray, measured: jnp.ndarray) -> dict:
    """Per-request latency/wait and steady-state aggregate bandwidth."""
    latency = sched.complete - issue_ps
    wait = jnp.sum(
        jnp.where(hops.valid, sched.start - sched.arrive[:, :-1], 0), axis=1
    )
    n_hops = jnp.sum(hops.valid, axis=1)
    t0 = jnp.min(jnp.where(measured, issue_ps, jnp.int64(1) << 60))
    t1 = jnp.max(jnp.where(measured, sched.complete, 0))
    span_ps = jnp.maximum(t1 - t0, 1)
    total_payload = jnp.sum(jnp.where(measured, payload_bytes, 0))
    bw_MBps = total_payload * PS_PER_S // (span_ps * 1_000_000)

    # steady-state bandwidth: completion rate inside the 30%..90% completion
    # quantile window (robust to warm-up ramp and drain tail, which an
    # open-loop flood necessarily has)
    comp_sorted = jnp.sort(sched.complete)
    n = comp_sorted.shape[0]
    lo, hi = (3 * n) // 10, (9 * n) // 10
    win = jnp.maximum(comp_sorted[hi] - comp_sorted[lo], 1)
    mean_pay = jnp.sum(payload_bytes) // jnp.maximum(n, 1)
    steady_bw_MBps = (hi - lo) * mean_pay * PS_PER_S // (win * 1_000_000)
    return {
        "latency_ps": latency,
        "queue_wait_ps": wait,
        "n_hops": n_hops,
        "span_ps": span_ps,
        "bandwidth_MBps": bw_MBps,
        "steady_bandwidth_MBps": steady_bw_MBps,
        "mean_latency_ps": jnp.sum(jnp.where(measured, latency, 0))
        // jnp.maximum(jnp.sum(measured), 1),
    }


def make_channels(graph, row_hit_ps: int = 0, row_miss_ps: int = 0) -> Channels:
    """Lift a FabricGraph's channel tables into engine form.

    Graphs whose links carry a flit config (`topology.LinkSpec.flit`)
    contribute the per-channel flit-mode tables; a graph with no flit links
    lowers to the seed's 4-field layout so ``flit_mode="none"`` stays
    structurally (and therefore jit-cache and bit-) identical.
    """
    c = graph.n_channels
    rh = np.where(graph.chan_is_service, row_hit_ps, 0).astype(np.int64)
    rm = np.where(graph.chan_is_service, row_miss_ps, 0).astype(np.int64)
    base = Channels(
        bw_MBps=jnp.asarray(graph.chan_bw_MBps),
        turnaround_ps=jnp.asarray(graph.chan_turnaround_ps),
        row_hit_ps=jnp.asarray(rh),
        row_miss_ps=jnp.asarray(rm),
    )
    fsize = getattr(graph, "chan_flit_size", None)
    if fsize is None or not np.any(np.asarray(fsize) > 0):
        return base
    return base._replace(
        flit_size=jnp.asarray(fsize),
        flit_payload=jnp.asarray(graph.chan_flit_payload),
        replay_ppm=jnp.asarray(graph.chan_replay_ppm),
    )
