"""Per-kernel correctness: shape/dtype sweeps, interpret-mode pallas vs the
pure-jnp oracle, plus hypothesis property tests for the engine hotspot."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp_compat import given, settings, st  # optional-hypothesis shim

import repro.core  # noqa: F401  (x64)
from repro.kernels.flash_attention.kernel import flash_attention_gqa
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.link_contention.kernel import segmented_depart
from repro.kernels.link_contention.ops import depart_times
from repro.kernels.link_contention.ref import segmented_depart_ref
from repro.kernels.rglru_scan.kernel import rglru_scan_pallas
from repro.kernels.rglru_scan.ref import rglru_scan_ref
from repro.kernels.ssd_chunk.kernel import ssd_chunk_pallas
from repro.kernels.ssd_chunk.ref import ssd_chunk_ref


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,kv,g,s,d,qb,kb", [
    (1, 2, 2, 256, 64, 128, 128),
    (2, 1, 4, 128, 128, 64, 128),
    (1, 4, 1, 512, 64, 256, 256),
])
def test_flash_attention_sweep(b, kv, g, s, d, qb, kb, dtype):
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (b, kv, g, s, d), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (b, kv, s, d), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (b, kv, s, d), jnp.float32).astype(dtype)
    out = flash_attention_gqa(q, k, v, causal=True, q_blk=qb, kv_blk=kb,
                              interpret=True)
    ref = flash_attention_ref(q, k, v, causal=True)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)


def test_flash_attention_windowed():
    ks = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(ks[0], (1, 2, 2, 256, 64), jnp.float32)
    k = jax.random.normal(ks[1], (1, 2, 256, 64), jnp.float32)
    v = jax.random.normal(ks[2], (1, 2, 256, 64), jnp.float32)
    out = flash_attention_gqa(q, k, v, causal=True, window=64,
                              q_blk=128, kv_blk=128, interpret=True)
    ref = flash_attention_ref(q, k, v, causal=True, window=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5,
                               rtol=2e-5)


def test_flash_ops_matches_model_layout():
    """The ops wrapper reproduces models.attention.plain_attention."""
    from repro.models.attention import plain_attention
    ks = jax.random.split(jax.random.key(2), 3)
    b, s, h, kvh, d = 2, 128, 8, 2, 64
    q = jax.random.normal(ks[0], (b, s, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, kvh, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, kvh, d), jnp.float32)
    out = flash_attention(q, k, v, causal=True, impl="interpret",
                          q_blk=64, kv_blk=64)
    ref = plain_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# rglru scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,s,d,chunk", [(2, 128, 64, 32), (1, 512, 256, 256),
                                         (3, 64, 128, 64)])
def test_rglru_scan_sweep(b, s, d, chunk):
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.uniform(0.8, 0.999, (b, s, d)).astype(np.float32))
    bb = jnp.asarray(rng.normal(0, 0.1, (b, s, d)).astype(np.float32))
    out = rglru_scan_pallas(a, bb, chunk=chunk, d_blk=min(d, 512),
                            interpret=True)
    ref = rglru_scan_ref(a, bb)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5,
                               rtol=1e-5)


def test_rglru_matches_model_block_semantics():
    """Kernel oracle == sequential recurrence (exact per-step check)."""
    rng = np.random.default_rng(1)
    b, s, d = 1, 37, 8
    a = rng.uniform(0.5, 0.99, (b, s, d)).astype(np.float32)
    bb = rng.normal(0, 1, (b, s, d)).astype(np.float32)
    ref = rglru_scan_ref(jnp.asarray(a), jnp.asarray(bb))
    h = np.zeros((b, d), np.float32)
    for t in range(s):
        h = a[:, t] * h + bb[:, t]
        np.testing.assert_allclose(np.asarray(ref[:, t]), h, atol=1e-5)


# ---------------------------------------------------------------------------
# ssd chunk
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,s,h,p,n,chunk", [
    (1, 128, 2, 32, 32, 64), (2, 256, 4, 64, 128, 128), (1, 64, 1, 16, 64, 32),
])
def test_ssd_chunk_sweep(b, s, h, p, n, chunk):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(0, 1, (b, s, h, p)).astype(np.float32))
    dt = jnp.asarray(rng.uniform(0.001, 0.1, (b, s, h)).astype(np.float32))
    al = jnp.asarray(np.log(rng.uniform(1, 8, h)).astype(np.float32))
    bm = jnp.asarray(rng.normal(0, 1, (b, s, n)).astype(np.float32))
    cm = jnp.asarray(rng.normal(0, 1, (b, s, n)).astype(np.float32))
    out = ssd_chunk_pallas(x, dt, al, bm, cm, chunk=chunk, interpret=True)
    ref = ssd_chunk_ref(x, dt, al, bm, cm, chunk=chunk)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5,
                               rtol=3e-4)


def test_ssd_chunk_invariance():
    """Chunk size must not change the result (state handoff exactness)."""
    rng = np.random.default_rng(2)
    b, s, h, p, n = 1, 256, 2, 16, 32
    x = jnp.asarray(rng.normal(0, 1, (b, s, h, p)).astype(np.float32))
    dt = jnp.asarray(rng.uniform(0.001, 0.1, (b, s, h)).astype(np.float32))
    al = jnp.asarray(np.log(rng.uniform(1, 8, h)).astype(np.float32))
    bm = jnp.asarray(rng.normal(0, 1, (b, s, n)).astype(np.float32))
    cm = jnp.asarray(rng.normal(0, 1, (b, s, n)).astype(np.float32))
    y64 = ssd_chunk_ref(x, dt, al, bm, cm, chunk=64)
    y256 = ssd_chunk_ref(x, dt, al, bm, cm, chunk=256)
    np.testing.assert_allclose(np.asarray(y64), np.asarray(y256), atol=2e-4,
                               rtol=2e-3)


# ---------------------------------------------------------------------------
# link contention (engine hotspot)
# ---------------------------------------------------------------------------

@given(st.integers(1, 12), st.integers(5, 400), st.integers(0, 2 ** 20),
       st.integers(1, 5))
@settings(max_examples=25, deadline=None)
def test_link_contention_property(nseg, k, tmax, seed):
    """Pallas blocked scan == sequential recurrence, exactly, for any sorted
    stream (hypothesis-driven)."""
    rng = np.random.default_rng(seed)
    chan = np.sort(rng.integers(0, nseg, k)).astype(np.int32)
    arrive = rng.integers(0, max(tmax, 1), k).astype(np.int32)
    order = np.lexsort((arrive, chan))
    chan, arrive = chan[order], arrive[order]
    ser = rng.integers(0, 1000, k).astype(np.int32)
    out = segmented_depart(jnp.asarray(chan), jnp.asarray(arrive),
                           jnp.asarray(ser), blk=128, interpret=True)
    ref = segmented_depart_ref(jnp.asarray(chan), jnp.asarray(arrive),
                               jnp.asarray(ser))
    assert np.array_equal(np.asarray(out), np.asarray(ref))


def test_depart_times_int64_rebase():
    rng = np.random.default_rng(3)
    k = 500
    chan = np.sort(rng.integers(0, 7, k)).astype(np.int64)
    arrive = (rng.integers(0, 1 << 20, k) + (7 << 40)).astype(np.int64)
    order = np.lexsort((arrive, chan))
    chan, arrive = chan[order], arrive[order]
    ser = rng.integers(0, 1000, k).astype(np.int64)
    out = depart_times(jnp.asarray(chan), jnp.asarray(arrive),
                       jnp.asarray(ser), impl="interpret")
    ref = depart_times(jnp.asarray(chan), jnp.asarray(arrive),
                       jnp.asarray(ser), impl="ref")
    assert np.array_equal(np.asarray(out), np.asarray(ref))
    assert np.asarray(out).min() >= (7 << 40)


# ---------------------------------------------------------------------------
# serve round (full engine round as a (max,+) affine scan)
# ---------------------------------------------------------------------------

from repro.core.engine import (  # noqa: E402
    Channels, Hops, SimOptions, simulate as engine_simulate)
from repro.core.ref_des import simulate_ref  # noqa: E402
from repro.core.streaming import simulate_stream, stream_windows  # noqa: E402
from repro.kernels.serve_round.kernel import (  # noqa: E402
    NEG, SPAN_LIMIT, serve_scan)
from repro.kernels.serve_round.ref import serve_scan_ref  # noqa: E402


@given(st.integers(8, 500), st.integers(0, 2 ** 31 - 1))
@settings(max_examples=25, deadline=None)
def test_serve_scan_property(k, seed):
    """Pallas Hillis-Steele composition scan == sequential lax.scan oracle,
    exactly, over random streams of the four map shapes the ops wrapper
    emits (head / serving / marker / pass-through).  Arbitrary saturated
    maps are NOT associative in the tropical -inf garbage region; the
    well-formed shapes keep the state non-negative from the head onward,
    which is the kernel's documented contract."""
    rng = np.random.default_rng(seed)

    def pick(hi):
        return rng.integers(0, hi, k).astype(np.int32)

    kind = rng.integers(0, 4, k)
    kind[0] = 0  # stream starts at a segment head
    neg = np.full(k, NEG, np.int32)
    zero = np.zeros(k, np.int32)
    # magnitudes keep the total round span inside the 2**29 contract
    s, gap, r, arr = pick(1 << 16), pick(1 << 16), pick(1 << 16), pick(1 << 20)
    has_r = rng.random(k) < 0.5
    rp = np.where(has_r, r, NEG)
    # serving map (kind 1)
    m00, m01, c0 = gap + s, s, arr + s
    m10 = np.maximum(m00 + rp, NEG)
    m11 = np.maximum(np.maximum(s + rp, 0), NEG)
    c1 = np.maximum(c0 + rp, NEG)
    # marker (kind 2): identity on depart, raise down to arr + r
    m00 = np.where(kind == 2, zero, m00)
    m01 = np.where(kind == 2, neg, m01)
    c0 = np.where(kind == 2, neg, c0)
    m10 = np.where(kind == 2, neg, m10)
    m11 = np.where(kind == 2, zero, m11)
    c1 = np.where(kind == 2, arr + r, c1)
    # pass-through (kind 3): full identity
    m00 = np.where(kind == 3, zero, m00)
    m01 = np.where(kind == 3, neg, m01)
    c0 = np.where(kind == 3, neg, c0)
    m10 = np.where(kind == 3, neg, m10)
    m11 = np.where(kind == 3, zero, m11)
    c1 = np.where(kind == 3, neg, c1)
    # head (kind 0): seed folded into c, incoming state killed
    m00 = np.where(kind == 0, neg, m00)
    m01 = np.where(kind == 0, neg, m01)
    m10 = np.where(kind == 0, neg, m10)
    m11 = np.where(kind == 0, neg, m11)
    c0 = np.where(kind == 0, arr, c0)
    c1 = np.where(kind == 0, arr + np.where(has_r, r, 0), c1)
    args = [jnp.asarray(a) for a in (m00, m01, m10, m11, c0, c1)]
    out, hi = serve_scan(*args, blk=64, interpret=True)
    ref = serve_scan_ref(*args)
    assert np.array_equal(np.asarray(out), np.asarray(ref))
    assert int(hi) <= SPAN_LIMIT


def _engine_case(seed, **kw):
    from test_engine import _random_case
    hops, ch, issue, _ = _random_case(seed, **kw)
    return hops, ch, issue


@pytest.mark.parametrize("seed", range(8))
def test_serve_round_kernel_bitexact_random(seed):
    """simulate(use_kernel='ref') == the lax-scan path == the oracle, on
    random demand configs with rows, turnaround flips and zero-byte hops."""
    hops, ch, issue = _engine_case(seed)
    lax_s = engine_simulate(hops, ch, jnp.asarray(issue))
    ker_s = engine_simulate(hops, ch, jnp.asarray(issue),
                            SimOptions(use_kernel="ref"))
    ref = simulate_ref(hops, ch, issue)
    for f in ("start", "depart", "arrive", "complete"):
        assert np.array_equal(np.asarray(getattr(lax_s, f)),
                              np.asarray(getattr(ker_s, f))), f
    assert np.array_equal(np.asarray(ker_s.complete), ref["complete"])


def test_serve_round_kernel_interpret_mode():
    """The actual Pallas kernel (interpret mode off-TPU) agrees with the
    lax path bit for bit."""
    hops, ch, issue = _engine_case(123)
    lax_s = engine_simulate(hops, ch, jnp.asarray(issue))
    pal_s = engine_simulate(hops, ch, jnp.asarray(issue),
                            SimOptions(use_kernel="interpret"))
    for f in ("start", "depart", "arrive", "complete"):
        assert np.array_equal(np.asarray(getattr(lax_s, f)),
                              np.asarray(getattr(pal_s, f))), f


@pytest.mark.parametrize("ber", [1e-4, 3e-4])
def test_serve_round_kernel_reliability_markers(ber):
    """Stochastic reliability configs: sampled replay bytes, retraining
    down-until clocks and link-down marker rows all flow through the
    (max,+) maps bit-exactly."""
    from test_link_reliability import _stochastic, _wl
    wl = _wl(_stochastic(ber), n=60)
    lax_s = engine_simulate(wl.hops, wl.channels, wl.issue_ps)
    ker_s = engine_simulate(wl.hops, wl.channels, wl.issue_ps,
                            SimOptions(use_kernel="ref"))
    ref = simulate_ref(wl.hops, wl.channels, wl.issue_ps)
    for f in ("start", "depart", "arrive", "complete"):
        assert np.array_equal(np.asarray(getattr(lax_s, f)),
                              np.asarray(getattr(ker_s, f))), f
    assert np.array_equal(np.asarray(ker_s.complete), ref["complete"])


@pytest.mark.parametrize("seed", range(4))
def test_serve_round_kernel_fork_join(seed):
    from test_engine import _join_case
    hops, ch, issue = _join_case(seed)
    lax_s = engine_simulate(hops, ch, jnp.asarray(issue))
    ker_s = engine_simulate(hops, ch, jnp.asarray(issue),
                            SimOptions(use_kernel="ref"))
    for f in ("start", "depart", "arrive", "complete"):
        assert np.array_equal(np.asarray(getattr(lax_s, f)),
                              np.asarray(getattr(ker_s, f))), f


def test_serve_round_kernel_stream_carry():
    """Windowed streaming with warm carries: the kernel path reproduces the
    monolithic lax schedule through every window boundary."""
    from test_engine import _join_case
    hops, ch, issue = _join_case(9)
    mono = engine_simulate(hops, ch, jnp.asarray(issue))
    out = simulate_stream(stream_windows(hops, np.asarray(issue), 6), ch,
                          options=SimOptions(use_kernel="ref"),
                          collect_schedule=True)
    assert out.converged
    col = out.collected
    r = col["item_row"].astype(np.int64)
    k = col["item_hop"].astype(np.int64)
    assert np.array_equal(col["item_depart"], np.asarray(mono.depart)[r, k])
    assert np.array_equal(col["item_start"], np.asarray(mono.start)[r, k])


@pytest.mark.parametrize("blk", [256, 2048])
def test_serve_scan_tiled_blocks(blk):
    """Blocks of several (rows, 128) tiles — the layout the chip runs —
    compose across lane wraps, sublane shifts and block carries exactly
    like the sequential oracle."""
    rng = np.random.default_rng(blk)
    k = 3 * blk + 77
    s = rng.integers(0, 1 << 12, k).astype(np.int32)
    arr = np.sort(rng.integers(0, 1 << 22, k)).astype(np.int32)
    head = rng.random(k) < 0.05
    head[0] = True
    neg = np.full(k, NEG, np.int32)
    m00 = np.where(head, neg, s)
    m01 = np.where(head, neg, s)
    m10 = neg.copy()
    m11 = np.where(head, neg, 0).astype(np.int32)
    c0 = arr + s
    c1 = np.where(head, arr, neg)
    args = [jnp.asarray(a) for a in (m00, m01, m10, m11, c0, c1)]
    out, hi = serve_scan(*args, blk=blk, interpret=True)
    assert np.array_equal(np.asarray(out), np.asarray(serve_scan_ref(*args)))
    assert int(hi) <= SPAN_LIMIT


def _one_channel_round(arrive, ser, impl):
    """`serve_round` on K serving items queued on one cold channel."""
    from repro.kernels.serve_round.ops import serve_round

    k = len(arrive)
    i64 = lambda x: jnp.asarray(np.asarray(x, np.int64))  # noqa: E731
    zero = i64(np.zeros(k))
    return serve_round(
        jnp.zeros(k, jnp.int32), jnp.ones(k, bool), jnp.zeros(k, bool),
        i64(arrive), jnp.zeros(k, jnp.int8), jnp.full(k, -1, jnp.int32),
        i64(ser), zero, zero, zero, zero, zero, jnp.full(k, -1, jnp.int8),
        jnp.full(k, -2, jnp.int32), zero, impl=impl)


@pytest.mark.parametrize("impl", ["ref", "interpret"])
@pytest.mark.parametrize("arrive,ser,ok", [
    ([0, 10, 20], [100, 100, 100], True),
    # queueing composes a depart of 3 * 2**28 > 2**29
    ([0, 0, 0], [1 << 28] * 3, False),
    # the rebased arrivals alone leave int32
    ([0, (1 << 31) + 5, (1 << 31) + 9], [100, 100, 100], False),
])
def test_serve_round_span_contract(impl, arrive, ser, ok):
    """A round over the 2**29 span is flagged, whichever way it breaks the
    contract; an in-contract round is exact."""
    start, depart, _, got = _one_channel_round(arrive, ser, impl)
    assert bool(got) is ok
    if ok:
        assert np.asarray(depart).tolist() == [100, 200, 300]
        assert np.asarray(start).tolist() == [0, 100, 200]


@pytest.mark.parametrize("impl", ["ref", "interpret"])
@pytest.mark.parametrize("issue,nbytes", [
    ([0, 0, 0, 0], 300_000),              # queued departs past 2**29 ps
    ([0, 3_000_000_000, 3_000_000_100, 3_000_000_200], 64),  # > int32 span
])
def test_serve_round_over_span_never_returns_kernel_schedule(impl, issue,
                                                             nbytes):
    """Synthetic over-span workloads: every round the kernel flags is
    resolved on the int64 lax path, so the schedule equals the lax engine
    and the event-driven oracle exactly."""
    n = len(issue)
    ch = Channels(jnp.asarray([1000], jnp.int64), jnp.zeros(1, jnp.int64),
                  jnp.zeros(1, jnp.int64), jnp.zeros(1, jnp.int64))
    ones = np.ones((n, 1))
    hops = Hops(jnp.zeros((n, 1), jnp.int32),
                jnp.asarray(ones * nbytes, jnp.int64),
                jnp.zeros((n, 1), jnp.int8), jnp.full((n, 1), -1, jnp.int32),
                jnp.asarray(ones * 500, jnp.int64), jnp.ones((n, 1), bool),
                jnp.ones((n, 1), bool))
    iss = jnp.asarray(issue, jnp.int64)
    lax_s = engine_simulate(hops, ch, iss)
    ker_s = engine_simulate(hops, ch, iss, SimOptions(use_kernel=impl))
    ref = simulate_ref(hops, ch, np.asarray(issue, np.int64))
    assert int(jnp.max(lax_s.arrive)) - min(issue) > SPAN_LIMIT
    for f in ("start", "depart", "arrive", "complete"):
        assert np.array_equal(np.asarray(getattr(lax_s, f)),
                              np.asarray(getattr(ker_s, f))), f
    assert np.array_equal(np.asarray(ker_s.complete), ref["complete"])
