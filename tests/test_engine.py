"""Schedule engine: exactness vs the event-driven oracle + conservation laws."""

import numpy as np
import pytest
from _hyp_compat import given, settings, st  # optional-hypothesis shim

import jax.numpy as jnp

import repro.core  # noqa: F401
from repro.core import engine
from repro.core.engine import (Channels, Hops, SimOptions, channel_stats,
                               empty_carry, hop_ser_ps, replay_round,
                               request_stats, round_bound, simulate,
                               simulate_auto)
from repro.core.ref_des import simulate_ref


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
    return hops, ch, issue, valid


@given(st.integers(0, 10_000))
@settings(max_examples=30, deadline=None)
def test_engine_exact_vs_oracle(seed):
    hops, ch, issue, valid = _random_case(seed)
    sched = simulate(hops, ch, jnp.asarray(issue))
    ref = simulate_ref(hops, ch, issue)
    assert bool(sched.converged)
    assert np.array_equal(np.asarray(sched.complete), ref["complete"])
    assert np.array_equal(np.asarray(sched.depart)[valid],
                          ref["depart"][valid])


def test_simulate_auto_oracle_fallback_matches():
    hops, ch, issue, _ = _random_case(7)
    # force the fallback by allowing a single round
    sched, used_oracle = simulate_auto(hops, ch, jnp.asarray(issue),
                                       SimOptions(max_rounds=1))
    ref = simulate_ref(hops, ch, issue)
    assert np.array_equal(np.asarray(sched.complete), ref["complete"])


def _tight_feedback_case(n=8000, h=8, c=2, seed=2):
    """Tight feedback: everything issued at t=0 onto two half-duplex
    channels with random direction flips — arrivals interleave requests and
    responses so the fixpoint resolves only a few queue positions per round
    and the default ``3*H + 8`` budget is insufficient."""
    rng = np.random.default_rng(seed)
    ch = Channels(jnp.asarray(rng.integers(10, 60, c).astype(np.int64) * 1000),
                  jnp.asarray(rng.integers(500, 5000, c).astype(np.int64)),
                  jnp.asarray(np.zeros(c, np.int64)),
                  jnp.asarray(np.zeros(c, np.int64)))
    chan = rng.integers(0, c, (n, h)).astype(np.int32)
    nb = rng.integers(1, 300, (n, h)).astype(np.int64)
    dirn = rng.integers(0, 2, (n, h)).astype(np.int8)
    fixed = rng.integers(0, 3000, (n, h)).astype(np.int64)
    valid = np.ones((n, h), bool)
    issue = np.zeros(n, np.int64)
    hops = Hops(jnp.asarray(chan), jnp.asarray(nb), jnp.asarray(dirn),
                jnp.asarray(np.full((n, h), -1, np.int32)),
                jnp.asarray(fixed), jnp.asarray(valid), jnp.asarray(valid))
    return hops, ch, issue


def test_simulate_auto_falls_back_on_natural_nonconvergence():
    """The oracle-fallback path under *natural* non-convergence: the default
    round budget genuinely runs out (no forced max_rounds) and simulate_auto
    must return the event-driven oracle's exact schedule."""
    hops, ch, issue = _tight_feedback_case()
    direct = simulate(hops, ch, jnp.asarray(issue))
    assert not bool(direct.converged), "case unexpectedly converged; " \
        "the fallback path is not being exercised"
    sched, used_oracle = simulate_auto(hops, ch, jnp.asarray(issue))
    assert used_oracle
    assert bool(sched.converged)
    ref = simulate_ref(hops, ch, issue)
    assert np.array_equal(np.asarray(sched.complete), ref["complete"])
    assert np.array_equal(np.asarray(sched.start), ref["start"])
    assert np.array_equal(np.asarray(sched.depart), ref["depart"])


# ---------------------------------------------------------------------------
# fork/join primitive: max-of-arrivals joins, engine == oracle bit-exact
# ---------------------------------------------------------------------------

def _join_case(seed, layers=3):
    """Random hop tables + a random layered join DAG: layer k rows feed
    groups that gate layer k+1 rows (contributor arity varies; some rows
    join nothing, one waiter rides an empty group)."""
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
    # split rows into layers; rows of layer k+1 wait on groups fed by
    # random subsets of layer k (strictly layered => DAG)
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
    # one waiter on an empty group: must issue at its own time
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


@pytest.mark.parametrize("seed", range(10))
def test_fork_join_engine_matches_oracle(seed):
    hops, ch, issue = _join_case(seed)
    sched = simulate(hops, ch, jnp.asarray(issue))
    ref = simulate_ref(hops, ch, issue)
    assert bool(sched.converged)
    assert np.array_equal(np.asarray(sched.complete), ref["complete"])
    assert np.array_equal(np.asarray(sched.start), ref["start"])
    assert np.array_equal(np.asarray(sched.depart), ref["depart"])


def test_join_waits_for_slowest_contributor():
    """Deterministic 3-row fan-in: the waiter issues exactly at the max of
    its contributors' completions (max-of-arrivals semantics)."""
    c = 3
    ch = Channels(jnp.asarray(np.full(c, 1000, np.int64)),
                  jnp.asarray(np.zeros(c, np.int64)),
                  jnp.asarray(np.zeros(c, np.int64)),
                  jnp.asarray(np.zeros(c, np.int64)))
    # rows 0,1 on distinct channels with different service; row 2 waits
    chan = np.array([[0], [1], [2]], np.int32)
    nbytes = np.array([[100], [300], [50]], np.int64)
    fixed = np.array([[7_000], [11_000], [0]], np.int64)
    valid = np.ones((3, 1), bool)
    hops = Hops(jnp.asarray(chan), jnp.asarray(nbytes),
                jnp.asarray(np.zeros((3, 1), np.int8)),
                jnp.asarray(np.full((3, 1), -1, np.int32)),
                jnp.asarray(fixed), jnp.asarray(valid), jnp.asarray(valid),
                join_id=jnp.asarray(np.array([1, 1, -1], np.int32)),
                join_wait=jnp.asarray(np.array([-1, -1, 1], np.int32)),
                join_arity=jnp.asarray(np.array([0, 0, 2], np.int32)))
    issue = jnp.asarray(np.array([0, 0, 0], np.int64))
    sched = simulate(hops, ch, issue)
    assert bool(sched.converged)
    comp = np.asarray(sched.complete)
    # ser = bytes*1e6/1000 MBps: row0 = 100_000+7_000, row1 = 300_000+11_000
    assert comp[0] == 107_000 and comp[1] == 311_000
    a2 = np.asarray(sched.arrive)[2, 0]
    assert a2 == max(comp[0], comp[1])       # slowest BIRsp releases the join
    assert comp[2] == a2 + 50_000
    ref = simulate_ref(hops, ch, np.asarray(issue))
    assert np.array_equal(comp, ref["complete"])


def test_join_cycle_deadlock_raises_in_oracle():
    """Cyclic join groups violate the DAG contract: the oracle detects the
    never-released waiters instead of silently dropping their rows."""
    c = 1
    ch = Channels(jnp.asarray(np.full(c, 1000, np.int64)),
                  jnp.asarray(np.zeros(c, np.int64)),
                  jnp.asarray(np.zeros(c, np.int64)),
                  jnp.asarray(np.zeros(c, np.int64)))
    ones = np.ones((2, 1), bool)
    hops = Hops(jnp.asarray(np.zeros((2, 1), np.int32)),
                jnp.asarray(np.full((2, 1), 10, np.int64)),
                jnp.asarray(np.zeros((2, 1), np.int8)),
                jnp.asarray(np.full((2, 1), -1, np.int32)),
                jnp.asarray(np.zeros((2, 1), np.int64)),
                jnp.asarray(ones), jnp.asarray(ones),
                join_id=jnp.asarray(np.array([0, 1], np.int32)),
                join_wait=jnp.asarray(np.array([1, 0], np.int32)),
                join_arity=jnp.asarray(np.array([1, 1], np.int32)))
    with pytest.raises(RuntimeError, match="join deadlock"):
        simulate_ref(hops, ch, np.zeros(2, np.int64))


def test_join_arity_contract_validated():
    """join_arity must equal the group's actual contributor count — the
    lowering contract the oracle enforces."""
    c = 1
    ch = Channels(jnp.asarray(np.full(c, 1000, np.int64)),
                  jnp.asarray(np.zeros(c, np.int64)),
                  jnp.asarray(np.zeros(c, np.int64)),
                  jnp.asarray(np.zeros(c, np.int64)))
    ones = np.ones((2, 1), bool)
    hops = Hops(jnp.asarray(np.zeros((2, 1), np.int32)),
                jnp.asarray(np.full((2, 1), 10, np.int64)),
                jnp.asarray(np.zeros((2, 1), np.int8)),
                jnp.asarray(np.full((2, 1), -1, np.int32)),
                jnp.asarray(np.zeros((2, 1), np.int64)),
                jnp.asarray(ones), jnp.asarray(ones),
                join_id=jnp.asarray(np.array([0, -1], np.int32)),
                join_wait=jnp.asarray(np.array([-1, 0], np.int32)),
                join_arity=jnp.asarray(np.array([0, 2], np.int32)))
    with pytest.raises(ValueError, match="join_arity"):
        simulate_ref(hops, ch, np.zeros(2, np.int64))


def test_channel_conservation():
    """No channel is busy more than wall-clock; payload time <= busy time."""
    hops, ch, issue, _ = _random_case(3)
    sched = simulate(hops, ch, jnp.asarray(issue))
    stats = channel_stats(hops, sched, ch)
    assert float(jnp.max(stats["utility"])) <= 1.0 + 1e-9
    assert np.all(np.asarray(stats["payload_ps"])
                  <= np.asarray(stats["busy_ps"]))


def test_latency_positive_and_fcfs_order():
    hops, ch, issue, valid = _random_case(11)
    sched = simulate(hops, ch, jnp.asarray(issue))
    r = request_stats(hops, sched, jnp.asarray(issue),
                      jnp.asarray(np.full(len(issue), 64)),
                      jnp.asarray(np.ones(len(issue), bool)))
    lat = np.asarray(r["latency_ps"])
    assert (lat >= 0).all()
    # starts never precede arrivals
    st_ = np.asarray(sched.start)[valid]
    ar = np.asarray(sched.arrive)[:, :-1][valid]
    assert (st_ >= ar).all()


# ---------------------------------------------------------------------------
# the round un-permutes its sorted outputs by a sort on their order
# ---------------------------------------------------------------------------

def _scatter_unpermute(order, *xs):
    """The scatters through ``order`` that `engine._unpermute` replaced."""
    k = order.shape[0]
    return tuple(jnp.zeros(k, jnp.int64).at[order].set(x) for x in xs)


def _retrain_case(seed):
    """`_random_case` with sampled retraining stalls on a fifth of the hops
    (no link-down markers: zero-byte hops carry none)."""
    hops, ch, issue, valid = _random_case(seed)
    rng = np.random.default_rng(seed + 1)
    retrain = np.where(rng.random(hops.channel.shape) < .2,
                       rng.integers(1, 4, hops.channel.shape) * 100_000, 0)
    retrain = np.where(np.asarray(hops.nbytes) == 0, 0, retrain)
    return hops._replace(retrain_after_ps=jnp.asarray(retrain, jnp.int64)), \
        ch, issue, valid


@pytest.mark.parametrize("case", [
    "helper-2", "helper-3",
    "fixpoint-scan", "fixpoint-scan-carry", "fixpoint-ref",
    "fixpoint-ref-carry",
    "replay-retrain",
])
def test_round_unpermutes_by_sort(case, monkeypatch):
    if case.startswith("helper"):
        # equal to the scatter for random permutations, int64 limits included
        n_arrays = int(case[-1])
        rng = np.random.default_rng(n_arrays)
        lo, hi = np.iinfo(np.int64).min, np.iinfo(np.int64).max
        for k in (1, 7, 4099):
            order = jnp.asarray(rng.permutation(k).astype(np.int32))
            xs = []
            for _ in range(n_arrays):
                x = rng.integers(lo, hi, k, dtype=np.int64, endpoint=True)
                x[rng.integers(0, k, 2)] = (lo, hi)
                x[rng.integers(0, k)] = hi - 1
                xs.append(jnp.asarray(x))
            got = engine._unpermute(order, *xs)
            want = _scatter_unpermute(order, *xs)
            assert len(got) == n_arrays
            for g, w in zip(got, want):
                assert g.dtype == jnp.int64
                assert np.array_equal(np.asarray(g), np.asarray(w))
    elif case.startswith("fixpoint"):
        # the fixpoint program, on both serve paths, still equals the oracle
        impl = case.split("-")[1]
        for seed in (5, 23):
            hops, ch, issue, valid = _random_case(seed)
            carry = (empty_carry(int(ch.bw_MBps.shape[0]))
                     if case.endswith("carry") else None)
            sched = engine._simulate_fixpoint(
                hops, ch, jnp.asarray(issue), hop_ser_ps(hops, ch),
                jnp.int64(round_bound(hops)), carry, impl=impl)
            ref = simulate_ref(hops, ch, issue)
            assert bool(sched.converged)
            assert np.array_equal(np.asarray(sched.complete), ref["complete"])
            for f in ("start", "depart"):
                assert np.array_equal(np.asarray(getattr(sched, f))[valid],
                                      ref[f][valid]), f
    else:
        # the telemetry replay on a retrain layout: the schedule reproduced,
        # and the stall table the scatters gave
        hops, ch, issue, _ = _retrain_case(9)
        sched = simulate(hops, ch, jnp.asarray(issue))
        assert bool(sched.converged)
        start, depart, stall = replay_round(hops, ch, sched)
        assert np.array_equal(np.asarray(start), np.asarray(sched.start))
        assert np.array_equal(np.asarray(depart), np.asarray(sched.depart))
        monkeypatch.setattr(engine, "_unpermute", _scatter_unpermute)
        _, _, want = replay_round(hops, ch, sched)
        assert int(jnp.sum(want)) > 0
        assert np.array_equal(np.asarray(stall), np.asarray(want))
