#!/usr/bin/env python3
"""Bring-up smoke run of the fabric simulator on one TPU chip.

Drives the simulator's main path once, through its public entry points, at
deployment sizes, in one process:

  A  rack fabric, monolithic: a spine-leaf fabric of PCIe 6.0 256 B-flit
     links, 16 hosts issuing 65,536 reads and writes to all 16 memory
     expanders, lowered by `build_workload` and resolved by `simulate_auto`;
     then latency attribution (conservation must be exact) and channel
     telemetry.  The 2,048 earliest requests are also resolved by the
     event-driven `ref_des` oracle and must match bit for bit.
  B  the §V-E open-loop trace of `benchmarks.bench_streaming`, streamed
     through `simulate_stream` in 4 windows of 65,536 rows (262,144
     requests), plus the bench's 2,000-row streamed-equals-monolithic check.
  C  phase A's workload and phase B's windows again through the Pallas
     serve-round kernel (`use_kernel="pallas"`); start, depart and arrive
     must equal the default path bit for bit, the compiled program must
     hold the Mosaic kernel, and every round must sit inside the kernel's
     int32 span contract (so no round fell back to the lax path).

The first call of each phase's fixpoint program is its compile: all of
them compile up front, in parallel threads, and each phase's timed call is
then a warm second call ending in ``block_until_ready`` (backend compiles
that still happen inside it are printed beside it).  These are the numbers
of one smoke run, not a benchmark.  The last line of standard output is the
JSON device record.  Any failed check raises; a first device that is not a
TPU exits 1 before anything runs.

Usage, from the repository root: ``python3 chip_smoke.py`` (no options).
The persistent compilation cache goes to ``$JAX_COMPILATION_CACHE_DIR`` or,
when that is unset, to ``.jax_cache/`` in the checkout.
"""

from __future__ import annotations

import functools
import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.compile_cache import use_compile_cache  # noqa: E402

# phase A: rack fabric
RACK_HOSTS = 16
RACK_REQUESTS = 65_536
RACK_INTERVAL_PS = 10_000      # per host: one request every 10 ns
RACK_READ_RATIO = 0.7
ORACLE_REQUESTS = 2_048
# phase B: streamed trace; every window holds exactly one chunk (the trace
# drains between chunks, which the carried-row check below pins), so all
# windows share one shape and one compiled program
STREAM_WINDOW = 65_536
STREAM_WINDOWS = 4
EQUIV_ROWS = 2_000
EQUIV_CHUNK = 256

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_compile_s = [0.0]


def _on_event(event: str, seconds: float, **_) -> None:
    if event == _BACKEND_COMPILE:
        _compile_s[0] += seconds


def check(cond, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


def same(a, b, what: str) -> None:
    check(np.array_equal(np.asarray(a), np.asarray(b)),
          f"{what} differs bit for bit")


def warm_call(phase: str, n_requests: int, compile_s: float, fn):
    """Time ``fn`` (a warm call: its program compiled up front) to
    ``block_until_ready`` and print the phase line."""
    c0 = _compile_s[0]
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    run = time.perf_counter() - t0
    print(f"[{phase}] one smoke run, not a benchmark: compile (first call) "
          f"{compile_s:.3f} s; warm call {run:.3f} s, "
          f"{n_requests / run:.0f} simulated requests/s ({n_requests} "
          f"requests; backend compiles inside the call "
          f"{_compile_s[0] - c0:.3f} s)", flush=True)
    return out


def rack_workload(n_requests: int):
    import repro.core as C
    from repro.core import calibration as cal

    endpoint = C.EndpointSpec(bw_MBps=cal.EXPANDER_MBPS, banks=16,
                              row_hit_extra_ps=cal.DRAM_ROW_HIT_PS,
                              row_miss_extra_ps=cal.DRAM_ROW_MISS_PS)
    topo = C.with_flit(C.spine_leaf(RACK_HOSTS,
                                    bw_MBps=cal.PCIE6_X16_RAW_MBPS,
                                    endpoint=endpoint), "flit256")
    mems = [int(m) for m in topo.memories()]
    per_host = n_requests // RACK_HOSTS
    specs = [C.RequesterSpec(node=int(r), n_requests=per_host, targets=mems,
                             read_ratio=RACK_READ_RATIO,
                             issue_interval_ps=RACK_INTERVAL_PS, seed=i)
             for i, r in enumerate(topo.requesters())]
    return C.build_workload(topo.build(), specs)


def oracle_subset(wl):
    """The ``ORACLE_REQUESTS`` earliest-issued rows of a workload."""
    import repro.core as C

    issue = np.asarray(wl.issue_ps)
    idx = np.sort(np.argsort(issue, kind="stable")[:ORACLE_REQUESTS])
    sub = C.Hops(*(None if f is None else jnp.asarray(np.asarray(f)[idx])
                   for f in wl.hops))
    return sub, jnp.asarray(issue[idx])


def compile_all(wl, sub, sub_issue, bench_ch):
    """Compile every large program the phases run, in parallel threads
    (the compiler releases the GIL); returns per-program ``(seconds,
    compiled)``.  The fixpoint calls then reuse them through the jit cache;
    the telemetry programs are called directly."""
    import repro.core as C
    from benchmarks.bench_streaming import _chunk
    from repro.core import empty_carry
    from repro.core.engine import _simulate_fixpoint, hop_ser_ps

    carry = empty_carry(int(bench_ch.bw_MBps.shape[0]))
    win_h, win_i = _chunk(0, STREAM_WINDOW, 0, seed=0)
    mono_h, mono_i = _chunk(0, EQUIV_ROWS, 0, seed=0)
    eq_h, eq_i = _chunk(0, 2 * EQUIV_CHUNK, 0, seed=0)
    budget = jnp.int64(1)   # the round budget is a traced operand
    fix = _simulate_fixpoint

    def ser(h):
        return jax.ShapeDtypeStruct(h.channel.shape, jnp.int64)

    sched = jax.eval_shape(functools.partial(fix, impl="scan"), wl.hops,
                           wl.channels, wl.issue_ps, ser(wl.hops), budget,
                           None)
    a = (wl.hops, wl.channels, wl.issue_ps, ser(wl.hops), budget, None)
    b = (win_h, bench_ch, win_i, ser(win_h), budget, carry)
    jobs = {
        "A/lax": (fix, *a, "scan"),
        "A/pallas": (fix, *a, "pallas"),
        "A/serialization": (hop_ser_ps, wl.hops, wl.channels),
        "A/attribution": (jax.jit(C.attribute_latency), wl.hops,
                          wl.channels, sched, wl.issue_ps),
        "A/telemetry": (jax.jit(C.channel_telemetry), wl.hops, wl.channels,
                        sched),
        "A/oracle-subset": (fix, sub, wl.channels, sub_issue, ser(sub),
                            budget, None, "scan"),
        "A/oracle-subset-ser": (hop_ser_ps, sub, wl.channels),
        "B/lax": (fix, *b, "scan"),
        "B/pallas": (fix, *b, "pallas"),
        "B/serialization": (hop_ser_ps, win_h, bench_ch),
        "B/equiv-mono": (fix, mono_h, bench_ch, mono_i, ser(mono_h), budget,
                         None, "scan"),
        "B/equiv-window": (fix, eq_h, bench_ch, eq_i, ser(eq_h), budget,
                           carry, "scan"),
    }

    def build(job):
        fn, *args = job
        t0 = time.perf_counter()
        compiled = fn.lower(*args).compile()
        return time.perf_counter() - t0, compiled

    t0 = time.perf_counter()
    with ThreadPoolExecutor(len(jobs)) as pool:
        futs = {name: pool.submit(build, job) for name, job in jobs.items()}
        out = {name: f.result() for name, f in futs.items()}
    print(f"[compile] {len(jobs)} programs in parallel threads: "
          f"{time.perf_counter() - t0:.3f} s wall; "
          + ", ".join(f"{k} {s:.3f} s" for k, (s, _) in out.items()),
          flush=True)
    for name in ("A/pallas", "B/pallas"):
        check("tpu_custom_call" in out[name][1].as_text(),
              f"{name} holds no Mosaic kernel")
    return out


def phase_a(wl, sub, sub_issue, compiled):
    import repro.core as C
    from repro.core import ref_des

    n = wl.hops.channel.shape[0]
    opts = C.SimOptions(check="oracle")
    sched, used_oracle = warm_call(
        "A", n, compiled["A/lax"][0],
        lambda: C.simulate_auto(wl.hops, wl.channels, wl.issue_ps, opts))
    check(used_oracle is False, "phase A fell back to the ref_des oracle")
    check(bool(sched.converged), "phase A fixpoint did not converge")
    check(int(sched.residual_ps) == 0, "phase A residual is not 0")
    print(f"[A] rounds {int(sched.rounds)}", flush=True)

    att = compiled["A/attribution"][1](wl.hops, wl.channels, sched,
                                       wl.issue_ps)
    resid = np.asarray(C.conservation_residual(att))
    check(np.all(resid == 0), "latency attribution does not conserve")
    tel = compiled["A/telemetry"][1](wl.hops, wl.channels, sched)
    util = np.asarray(tel.utilization)
    check(np.all(np.isfinite(util)) and util.min() >= 0 and util.max() <= 1,
          "channel utilization outside [0, 1]")
    check(int(np.asarray(tel.busy_ps).sum()) > 0, "no channel was busy")
    print(f"[A] max channel utilization {util.max():.4f}, peak backlog "
          f"{int(np.asarray(tel.peak_backlog).max())}", flush=True)
    stats = jax.devices()[0].memory_stats()
    if stats:
        print(f"[A] device bytes in use {stats.get('bytes_in_use')}, peak "
              f"{stats.get('peak_bytes_in_use')}, limit "
              f"{stats.get('bytes_limit')}", flush=True)

    eng = C.simulate(sub, wl.channels, sub_issue)
    ref = ref_des.simulate_ref(sub, wl.channels, np.asarray(sub_issue))
    check(bool(eng.converged), "oracle subset did not converge")
    for f in ("arrive", "start", "depart", "complete"):
        same(getattr(eng, f), ref[f], f"[A] engine vs ref_des {f}")
    print(f"[A] {ORACLE_REQUESTS} earliest requests equal ref_des bit for "
          f"bit", flush=True)
    return sched


def stream_trace(ch, compile_s: float, phase: str, use_kernel=False):
    import repro.core as C
    from benchmarks.bench_streaming import _trace

    n = STREAM_WINDOWS * STREAM_WINDOW
    return warm_call(phase, n, compile_s, lambda: C.simulate_stream(
        _trace(n, STREAM_WINDOW), ch, pad_to=STREAM_WINDOW,
        collect_schedule=True,
        options=C.SimOptions(check="static", use_kernel=use_kernel)))


def phase_b(ch, compiled):
    from benchmarks.bench_streaming import H, _chunk
    from repro.core import assert_valid, simulate, simulate_stream
    from repro.core import stream_windows

    n = STREAM_WINDOWS * STREAM_WINDOW
    res = stream_trace(ch, compiled["B/lax"][0], "B")
    s = res.summary()
    check(res.windows == STREAM_WINDOWS, f"{res.windows} windows")
    check(res.oracle_windows == 0, f"{res.oracle_windows} oracle windows")
    check(s["n_retired"] == n, f"retired {s['n_retired']} of {n}")
    check(s["windows_converged"] == res.windows, "a window did not converge")
    check(res.carried_peak == 0, "rows carried across a window edge: the "
          "windows no longer share one shape")
    p50, p99, p999 = (int(q) for q in s["quantiles_ps"])
    check(0 < p50 <= p99 <= p999, "tail quantiles out of order")
    print(f"[B] {res.windows} windows of {STREAM_WINDOW} rows, rounds max "
          f"{s['rounds_max']}, p50/p99/p99.9 {p50}/{p99}/{p999} ps",
          flush=True)

    small_h, small_i = _chunk(0, EQUIV_ROWS, 0, seed=0)
    assert_valid(small_h, ch, small_i)
    mono = simulate(small_h, ch, small_i)
    check(bool(mono.converged), "2,000-row monolithic run did not converge")
    out = simulate_stream(
        stream_windows(small_h, np.asarray(small_i), EQUIV_CHUNK), ch,
        pad_to=2 * EQUIV_CHUNK, collect_schedule=True)
    col = out.collected
    r = col["item_row"].astype(np.int64)
    k = col["item_hop"].astype(np.int64)
    check(r.size == EQUIV_ROWS * H, "settled items folded more or less "
          "than once")
    for f in ("start", "depart", "arrive"):
        same(col[f"item_{f}"], np.asarray(getattr(mono, f))[r, k],
             f"[B] streamed vs monolithic {f}")
    same(col["row_complete"],
         np.asarray(mono.complete)[col["row_id"].astype(np.int64)],
         "[B] streamed vs monolithic completion")
    print(f"[B] {EQUIV_ROWS}-row stream equals the monolithic run bit for "
          f"bit", flush=True)
    return res


def phase_c(wl, sched, ch, stream, compiled):
    import repro.core as C
    from repro.kernels.serve_round.kernel import SPAN_LIMIT

    pal = C.SimOptions(check="off", use_kernel="pallas")
    kern = warm_call(
        "C rack", wl.hops.channel.shape[0], compiled["A/pallas"][0],
        lambda: C.simulate(wl.hops, wl.channels, wl.issue_ps, pal))
    check(bool(kern.converged), "phase C fixpoint did not converge")
    for f in ("start", "depart", "arrive"):
        same(getattr(kern, f), getattr(sched, f), f"[C] pallas vs lax {f}")
    # every round's rebased span is at most the converged one (rounds are
    # monotone lower bounds), so no round left the kernel
    span = int(jnp.max(sched.arrive)) - int(jnp.min(wl.issue_ps))
    check(span <= SPAN_LIMIT, f"rack span {span} ps breaks the contract")

    kout = stream_trace(ch, compiled["B/pallas"][0], "C stream", "pallas")
    check(kout.oracle_windows == 0 and kout.converged,
          "a phase C window fell back to the oracle")
    for f in ("item_row", "item_hop", "item_start", "item_depart",
              "item_arrive", "row_id", "row_complete"):
        same(kout.collected[f], stream.collected[f], f"[C] window {f}")
    # each window holds one chunk (no carried rows), so its rounds span
    # at most that chunk's first issue to its last completion
    col = kout.collected
    for w in range(STREAM_WINDOWS):
        lo = int(col["item_arrive"][col["item_row"] // STREAM_WINDOW
                                    == w].min())
        span = int(col["row_complete"][col["row_id"] // STREAM_WINDOW
                                       == w].max()) - lo
        check(span <= SPAN_LIMIT, f"window {w} span {span} ps breaks the "
              "contract")


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: first device is {dev.platform!r}, not a TPU",
              file=sys.stderr)
        return 1
    cache = use_compile_cache(ROOT)
    jax.monitoring.register_event_duration_secs_listener(_on_event)
    print(f"device {dev.device_kind} x{len(jax.devices())}, jax "
          f"{jax.__version__}, compile cache {cache}", flush=True)

    from benchmarks.bench_streaming import _channels

    t0 = time.perf_counter()
    wl = rack_workload(RACK_REQUESTS)
    sub, sub_issue = oracle_subset(wl)
    n, h = wl.hops.channel.shape
    print(f"[A] rack fabric: {n} requests x {h} hops lowered in "
          f"{time.perf_counter() - t0:.3f} s", flush=True)
    ch = _channels()
    compiled = compile_all(wl, sub, sub_issue, ch)
    sched = phase_a(wl, sub, sub_issue, compiled)
    stream = phase_b(ch, compiled)
    phase_c(wl, sched, ch, stream, compiled)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
