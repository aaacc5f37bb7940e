"""Streaming windowed engine: windowed == monolithic bit-for-bit at every
window size (the correctness contract), across turnaround/row/zero-byte
tables, stochastic reliability (sampled replay bursts + retraining markers),
and fork/join DAGs; streamed telemetry folds equal the monolithic counters
and sketch; chunk-stream contracts are enforced; protocol-state threading
makes chunked SF / coherence runs exact."""

import numpy as np
import pytest
from _hyp_compat import given, settings, st  # optional-hypothesis shim

import jax.numpy as jnp

import repro.core  # noqa: F401  (x64)
from repro.core import topology as T
from repro.core.coherence_traffic import (CoherenceFabricSpec,
                                          CoherenceStream, coherence_issue,
                                          lower_coherence)
from repro.core.devices import RequesterSpec, build_workload
from repro.core.engine import (Channels, Hops, SimOptions, empty_carry,
                               simulate, simulate_auto)
from repro.core.link_layer import FlitConfig
from repro.core.snoop_filter import (CacheConfig, SFConfig, make_skewed_stream,
                                     sf_init_state, simulate_sf)
from repro.core.streaming import StreamState, simulate_stream, stream_windows
from repro.core.telemetry import (channel_telemetry, sketch_new,
                                  sketch_quantiles, sketch_update)

WINDOWS = (1, 3, 7, 1000)


# ---------------------------------------------------------------------------
# case builders (mirroring test_engine / test_link_reliability families)
# ---------------------------------------------------------------------------

def _random_case(seed, with_rows=True, with_turnaround=True, zero_bytes=True):
    rng = np.random.default_rng(seed)
    n, h, c = int(rng.integers(3, 40)), int(rng.integers(1, 7)), int(rng.integers(1, 6))
    bw = rng.integers(10, 100, c).astype(np.int64) * 1000
    turn = (np.where(rng.random(c) < .5, rng.integers(100, 5000, c), 0)
            if with_turnaround else np.zeros(c)).astype(np.int64)
    rowm = np.zeros(c, bool)
    if with_rows:
        rowm[-1] = True
    ch = Channels(jnp.asarray(bw), jnp.asarray(turn),
                  jnp.asarray(np.where(rowm, 1000, 0).astype(np.int64)),
                  jnp.asarray(np.where(rowm, 9000, 0).astype(np.int64)))
    chan = rng.integers(0, c, (n, h)).astype(np.int32)
    nbytes = rng.integers(1, 300, (n, h)).astype(np.int64)
    if zero_bytes:
        nbytes = np.where(rng.random((n, h)) < 0.2, 0, nbytes)
    dirn = rng.integers(0, 2, (n, h)).astype(np.int8)
    row = np.where((chan == c - 1) & rowm[-1],
                   rng.integers(0, 3, (n, h)), -1).astype(np.int32)
    fixed = rng.integers(0, 2000, (n, h)).astype(np.int64)
    valid = rng.random((n, h)) < .85
    issue = np.sort(rng.integers(0, 5000, n)).astype(np.int64)
    hops = Hops(jnp.asarray(chan), jnp.asarray(nbytes), jnp.asarray(dirn),
                jnp.asarray(row), jnp.asarray(fixed), jnp.asarray(valid),
                jnp.asarray(valid))
    return hops, ch, issue


def _reliability_case(seed):
    """Randomized replay/retraining tables over mixed byte-exact and flit
    channels — link-down markers included (zero-byte retrain hops)."""
    rng = np.random.default_rng(seed)
    n, h, c = int(rng.integers(3, 24)), int(rng.integers(1, 6)), \
        int(rng.integers(2, 6))
    bw = rng.integers(10, 100, c).astype(np.int64) * 1000
    turn = np.where(rng.random(c) < .5,
                    rng.integers(100, 5000, c), 0).astype(np.int64)
    fsize = rng.choice([0, 68, 256], c).astype(np.int64)
    fpay = np.where(fsize == 68, 64,
                    np.where(fsize == 256, 236, 0)).astype(np.int64)
    ch = Channels(jnp.asarray(bw), jnp.asarray(turn),
                  jnp.asarray(np.zeros(c, np.int64)),
                  jnp.asarray(np.zeros(c, np.int64)),
                  flit_size=jnp.asarray(fsize),
                  flit_payload=jnp.asarray(fpay),
                  replay_ppm=jnp.asarray(np.zeros(c, np.int64)))
    chan = rng.integers(0, c, (n, h)).astype(np.int32)
    nbytes = rng.integers(0, 1200, (n, h)).astype(np.int64)
    valid = rng.random((n, h)) < .85
    extra = np.where(rng.random((n, h)) < .3,
                     rng.integers(0, 8, (n, h)) * 256, 0).astype(np.int64)
    retrain = np.where(rng.random((n, h)) < .2,
                       rng.integers(1, 4, (n, h)) * 100_000, 0).astype(np.int64)
    # a zero-byte retrain hop is a link-down marker, which the lowering
    # contract (`verify`, rel.marker) allows on full-duplex channels only
    retrain = np.where((nbytes == 0) & (turn[chan] != 0), 0, retrain)
    hops = Hops(jnp.asarray(chan), jnp.asarray(nbytes),
                jnp.asarray(rng.integers(0, 2, (n, h)).astype(np.int8)),
                jnp.asarray(np.full((n, h), -1, np.int32)),
                jnp.asarray(rng.integers(0, 2000, (n, h)).astype(np.int64)),
                jnp.asarray(valid), jnp.asarray(valid),
                extra_wire_bytes=jnp.asarray(extra),
                retrain_after_ps=jnp.asarray(retrain))
    issue = np.sort(rng.integers(0, 5000, n)).astype(np.int64)
    return hops, ch, issue


def _join_case(seed, layers=3):
    """Random hop tables + a layered join DAG (varying arity, one waiter on
    an empty group)."""
    rng = np.random.default_rng(seed)
    n, h, c = int(rng.integers(12, 36)), int(rng.integers(2, 5)), int(rng.integers(2, 5))
    bw = rng.integers(10, 100, c).astype(np.int64) * 1000
    ch = Channels(jnp.asarray(bw),
                  jnp.asarray(np.where(rng.random(c) < .4,
                                       rng.integers(100, 4000, c), 0)
                              .astype(np.int64)),
                  jnp.asarray(np.zeros(c, np.int64)),
                  jnp.asarray(np.zeros(c, np.int64)))
    chan = rng.integers(0, c, (n, h)).astype(np.int32)
    nbytes = rng.integers(1, 400, (n, h)).astype(np.int64)
    nbytes = np.where(rng.random((n, h)) < 0.15, 0, nbytes)
    valid = rng.random((n, h)) < .85
    jid = np.full(n, -1, np.int32)
    jwait = np.full(n, -1, np.int32)
    jarity = np.zeros(n, np.int32)
    bounds = np.sort(rng.choice(np.arange(1, n), layers, replace=False))
    layer_rows = np.split(np.arange(n), bounds)
    grp = 0
    for up, dn in zip(layer_rows[:-1], layer_rows[1:]):
        for w in dn:
            if rng.random() < 0.5:
                members = up[rng.random(up.shape[0]) < 0.5]
                members = members[jid[members] < 0]
                if members.size == 0:
                    continue
                jid[members] = grp
                jwait[w] = grp
                jarity[w] = members.size
                grp += 1
    free = np.nonzero(jwait < 0)[0]
    if free.size:
        jwait[free[-1]] = grp
        jarity[free[-1]] = 0
    hops = Hops(jnp.asarray(chan), jnp.asarray(nbytes),
                jnp.asarray(rng.integers(0, 2, (n, h)).astype(np.int8)),
                jnp.asarray(np.full((n, h), -1, np.int32)),
                jnp.asarray(rng.integers(0, 2000, (n, h)).astype(np.int64)),
                jnp.asarray(valid), jnp.asarray(valid),
                join_id=jnp.asarray(jid), join_wait=jnp.asarray(jwait),
                join_arity=jnp.asarray(jarity))
    issue = np.sort(rng.integers(0, 5000, n)).astype(np.int64)
    return hops, ch, issue


def _stream_check(hops, ch, issue, window):
    """Windowed run == monolithic run, bit for bit: every valid item's
    (start, depart, arrive) exactly once, every row's completion and gated
    first-hop arrival."""
    mono = simulate(hops, ch, jnp.asarray(issue))
    assert bool(mono.converged)
    out = simulate_stream(stream_windows(hops, issue, window), ch,
                          collect_schedule=True)
    col = out.collected
    v = np.asarray(hops.valid)
    n, h = v.shape
    assert out.n_rows == n

    r = col["item_row"].astype(np.int64)
    k = col["item_hop"].astype(np.int64)
    got = set(zip(r.tolist(), k.tolist()))
    assert len(got) == r.size                      # folded exactly once
    assert got == {(int(i), int(j)) for i, j in zip(*np.nonzero(v))}
    ms, md, ma = map(np.asarray, (mono.start, mono.depart, mono.arrive))
    assert np.array_equal(col["item_start"], ms[r, k])
    assert np.array_equal(col["item_depart"], md[r, k])
    assert np.array_equal(col["item_arrive"], ma[r, k])

    rr = col["row_id"].astype(np.int64)
    assert np.array_equal(np.sort(rr), np.arange(n))   # every row retires once
    assert np.array_equal(col["row_complete"], np.asarray(mono.complete)[rr])
    gr = col["gate_row"].astype(np.int64)
    assert np.array_equal(col["gate_arrive0"], ma[gr, 0])
    return mono, out


# ---------------------------------------------------------------------------
# the correctness contract: windowed == monolithic at any window size
# ---------------------------------------------------------------------------

@given(st.integers(0, 10_000), st.sampled_from(WINDOWS))
@settings(max_examples=25, deadline=None)
def test_stream_equals_monolithic_random(seed, window):
    hops, ch, issue = _random_case(seed)
    _stream_check(hops, ch, issue, window)


@pytest.mark.parametrize("seed", range(6))
@pytest.mark.parametrize("window", (1, 5))
def test_stream_equals_monolithic_reliability(seed, window):
    hops, ch, issue = _reliability_case(seed)
    _stream_check(hops, ch, issue, window)


@given(st.integers(0, 10_000), st.sampled_from(WINDOWS))
@settings(max_examples=25, deadline=None)
def test_stream_equals_monolithic_joins(seed, window):
    hops, ch, issue = _join_case(seed)
    _stream_check(hops, ch, issue, window)


def test_stream_equals_monolithic_built_workload_markers():
    """The full build path: stochastic flit reliability whose retraining
    stalls insert full-duplex mirror markers into the hop table."""
    topo = T.with_flit(T.single_bus(n_mems=4, bw_MBps=128_000),
                       FlitConfig("flit256", ber=3e-4,
                                  reliability="stochastic", rel_seed=7,
                                  retrain_threshold=2, retrain_ps=1_000_000))
    spec = RequesterSpec(node=0, n_requests=150, targets=[2, 3, 4, 5],
                         read_ratio=0.5, issue_interval_ps=300,
                         payload_bytes=944, seed=3)
    wl = build_workload(topo.build(), [spec], warmup_frac=0.0)
    assert np.asarray(wl.hops.retrain_after_ps).any()
    _stream_check(wl.hops, wl.channels, np.asarray(wl.issue_ps), 17)


# ---------------------------------------------------------------------------
# streamed telemetry fold == monolithic counters and sketch
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("window", (1, 6))
def test_stream_telemetry_matches_monolithic(window):
    hops, ch, issue = _reliability_case(3)
    mono, out = _stream_check(hops, ch, issue, window)
    tel = channel_telemetry(hops, ch, mono)
    acc = out.telemetry
    for sf, mf in (("payload_bytes", "payload_bytes"),
                   ("wire_bytes", "wire_bytes"), ("busy_ps", "busy_ps"),
                   ("wait_ps", "wait_ps")):
        assert np.array_equal(np.asarray(getattr(acc, sf)),
                              np.asarray(getattr(tel, mf))), sf
    h = np.asarray(hops.valid).shape[1]
    lat = np.asarray(mono.arrive)[:, h] - issue
    sk = sketch_update(sketch_new(), jnp.asarray(lat),
                       mask=jnp.ones(lat.shape, bool))
    assert np.array_equal(np.asarray(sketch_quantiles(acc.sketch)),
                          np.asarray(sketch_quantiles(sk)))
    assert int(acc.n_retired) == lat.shape[0]
    s = out.summary()
    assert s["n_retired"] == lat.shape[0] and s["windows"] == out.windows


# ---------------------------------------------------------------------------
# chunk-stream contracts
# ---------------------------------------------------------------------------

def test_stream_windows_never_split_join_groups():
    hops, ch, issue = _join_case(11)
    for w in (1, 2, 3):
        for ck, _ in stream_windows(hops, issue, w):
            jid = np.asarray(ck.join_id)
            jw = np.asarray(ck.join_wait)
            ja = np.asarray(ck.join_arity)
            for g in np.unique(jw[jw >= 0]):
                # every waiter's arity is satisfied inside its own chunk
                assert (jid == g).sum() == ja[jw == g].max()


def test_out_of_order_chunk_stream_rejected():
    hops, ch, issue = _random_case(1)
    chunks = list(stream_windows(hops, issue, 10))[::-1]
    if len(chunks) > 1:
        with pytest.raises(ValueError, match="out of order"):
            simulate_stream(chunks, ch)


def test_mixed_layout_chunk_stream_rejected():
    h1, ch, i1 = _random_case(2)
    h2, _, i2 = _reliability_case(2)
    with pytest.raises(ValueError, match="layout"):
        simulate_stream([(h1, i1 - i1.min()), (h2, i2 + i1.max())],
                        Channels(ch.bw_MBps, ch.turnaround_ps,
                                 ch.row_hit_ps, ch.row_miss_ps))


def test_stream_state_resumes_across_calls():
    """Two `simulate_stream` calls with the state handed across equal one
    call when the split lands on a quiescent boundary (each call drains its
    own rows, so a split is exact iff nothing later could have contended —
    here the second segment issues after a gap longer than any makespan)."""
    hops, ch, issue = _random_case(33)
    early = list(stream_windows(hops, issue, 4))
    late = list(stream_windows(hops, issue + 2_000_000_000, 4))
    one = simulate_stream(early + late, ch)
    state = StreamState(ch)
    a = simulate_stream(early, ch, state)
    b = simulate_stream(late, ch, state)
    assert b.n_rows == one.n_rows
    assert int(b.telemetry.n_retired) == int(one.telemetry.n_retired)
    assert np.array_equal(np.asarray(b.telemetry.busy_ps),
                          np.asarray(one.telemetry.busy_ps))
    assert np.array_equal(np.asarray(sketch_quantiles(b.telemetry.sketch)),
                          np.asarray(sketch_quantiles(one.telemetry.sketch)))


# ---------------------------------------------------------------------------
# engine carry API
# ---------------------------------------------------------------------------

def test_empty_carry_is_identity():
    hops, ch, issue = _random_case(5)
    base = simulate(hops, ch, jnp.asarray(issue))
    c = int(ch.bw_MBps.shape[0])
    seeded = simulate(hops, ch, jnp.asarray(issue),
                      carry=empty_carry(c))
    for f in ("start", "depart", "arrive", "complete"):
        assert np.array_equal(np.asarray(getattr(base, f)),
                              np.asarray(getattr(seeded, f))), f
    hj, chj, ij = _join_case(5)
    bj = simulate(hj, chj, jnp.asarray(ij))
    sj = simulate(hj, chj, jnp.asarray(ij),
                  carry=empty_carry(int(chj.bw_MBps.shape[0]),
                                    int(hj.channel.shape[0])))
    assert np.array_equal(np.asarray(bj.complete), np.asarray(sj.complete))


def test_simulate_auto_check_flag_skips_fallback():
    hops, ch, issue = _random_case(7)
    # forced non-convergence: the default falls back to the oracle ...
    sched, used = simulate_auto(hops, ch, jnp.asarray(issue),
                                SimOptions(max_rounds=1))
    assert used and bool(sched.converged)
    # ... check='off' returns the raw fixpoint without the host sync
    raw, used = simulate_auto(hops, ch, jnp.asarray(issue),
                              SimOptions(max_rounds=1, check="off"))
    assert not used and not bool(raw.converged)
    # on a converged run check='off' is the same schedule
    full, used = simulate_auto(hops, ch, jnp.asarray(issue),
                               SimOptions(check="off"))
    ref, _ = simulate_auto(hops, ch, jnp.asarray(issue))
    assert not used
    assert np.array_equal(np.asarray(full.complete), np.asarray(ref.complete))


# ---------------------------------------------------------------------------
# protocol-state threading: chunked SF / coherence == monolithic
# ---------------------------------------------------------------------------

def test_sf_state_threading_bitexact():
    cfg = SFConfig(capacity=16, footprint_lines=256, policy="lru")
    ccfg = CacheConfig(capacity=8)
    addr, wr, _ = make_skewed_stream(400, 256, seed=3)
    rid = jnp.asarray(np.arange(400) % 3, jnp.int32)
    mono, mev = simulate_sf(addr, wr, rid, cfg, ccfg, n_requesters=3,
                            return_events=True)
    st_ = sf_init_state(cfg, ccfg, 3)
    lats, fabs = [], []
    for lo in range(0, 400, 97):
        hi = min(lo + 97, 400)
        r, ev, st_ = simulate_sf(addr[lo:hi], wr[lo:hi], rid[lo:hi], cfg,
                                 ccfg, n_requesters=3, return_events=True,
                                 init_state=st_, return_state=True)
        lats.append(np.asarray(r.latency_ps))
        fabs.append(np.asarray(ev.fab_issue_ps))
    assert np.array_equal(np.concatenate(lats), np.asarray(mono.latency_ps))
    assert np.array_equal(np.concatenate(fabs), np.asarray(mev.fab_issue_ps))
    assert int(st_.bisnp) == int(mono.bisnp_events)
    assert int(jnp.max(st_.clock)) == int(mono.total_time_ps)


def test_coherence_stream_matches_monolithic():
    kinds = [T.SWITCH, T.REQUESTER, T.REQUESTER, T.MEMORY]
    links = [T.LinkSpec(i, 0, 64_000, 26_000) for i in (1, 2, 3)]
    graph = T.Topology(np.asarray(kinds, np.int64), links,
                       name="star").build()
    spec = CoherenceFabricSpec(dev_node=3, req_nodes=(1, 2))
    sf_cfg = SFConfig(capacity=16, footprint_lines=256, policy="lru")
    ccfg = CacheConfig(capacity=8)
    addr, wr, rid = make_skewed_stream(420, 256, write_ratio=0.3,
                                       n_requesters=2, seed=4)
    res, ev = simulate_sf(addr, wr, rid, sf_cfg, ccfg, n_requesters=2,
                          return_events=True)
    low = lower_coherence(graph, spec, sf_cfg, np.asarray(addr),
                          np.asarray(wr), np.asarray(rid), ev,
                          fanout="chain")
    cs = CoherenceStream(addr, wr, rid, sf_cfg, ccfg, graph, spec,
                         chunk=101, n_requesters=2, fanout="chain")
    ch = cs.channels()
    mono = simulate(low.hops, ch, coherence_issue(low, ev.fab_issue_ps))
    assert bool(mono.converged)
    out = simulate_stream(cs, ch, collect_schedule=True)
    col = out.collected
    ma = np.asarray(mono.arrive)
    r = col["item_row"].astype(np.int64)
    k = col["item_hop"].astype(np.int64)
    got = set(zip(r.tolist(), k.tolist()))
    assert got == {(int(i), int(j))
                   for i, j in zip(*np.nonzero(np.asarray(low.hops.valid)))}
    assert np.array_equal(col["item_start"], np.asarray(mono.start)[r, k])
    assert np.array_equal(col["item_depart"], np.asarray(mono.depart)[r, k])
    assert np.array_equal(col["item_arrive"], ma[r, k])
    rr = col["row_id"].astype(np.int64)
    assert np.array_equal(col["row_complete"], np.asarray(mono.complete)[rr])
    assert cs.n_done == 420 and out.n_rows == low.hops.channel.shape[0]
