"""The harness end to end on the CPU at a tiny size, the serve round in
Pallas interpret mode: a sound run is correct; a run with the timed path
broken underneath is not; each control (the reference in the program's
place, with the FCFS ties reversed) fails the comparison; the measuring
path refuses a platform that is not a TPU."""

import functools
import os

import pytest

from chipbench import registry
from chipbench import run as harness

# about 1,100 rows are in flight at any time at the rack's load, so a tiny
# stream window holds a chunk and what it carries in 4,096 rows
TINY = {
    "rack16.mono": {"requests_per_host": 64, "footprint_lines": 64},
    "rack16.stream": {"requests_per_host": 64, "footprint_lines": 64,
                      "chunks": 3, "window_rows": 4096},
}
# every rack time is a multiple of 8 ps and stays under 2**27 ps at the
# cells' sizes, where a float32 clock is exact, so the control that bites
# breaks the FCFS ties
CONTROL = {
    ("rack16.mono", "reverse_ties"): {"requests_per_host": 256,
                                      "footprint_lines": 256},
    ("rack16.stream", "reverse_ties"): TINY["rack16.stream"],
}
SEED = 2**31 + 977


@pytest.fixture(scope="module")
def bench():
    return harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))


def run_cell(bench, cell, seed=SEED, trace=False):
    from repro.core import SimOptions

    return harness.run(bench, cell, seed, 0.2, trace,
                       options=SimOptions(use_kernel="interpret"),
                       peaks={"cpu": {"hbm_bytes_per_s": 1e11}}, cache=False,
                       sizes=TINY[cell])


@pytest.mark.parametrize("cell", sorted(TINY))
@pytest.mark.parametrize("trace", [False, True])
def test_sound_run_is_correct(bench, cell, trace):
    res = run_cell(bench, cell, trace=trace)
    assert res["correct"] is True
    assert all(c["value"] == 0 for c in res["checks"].values())
    assert list(res)[-1] == "checks"
    assert res["counters"]["window_compiles"] == 0
    assert res["counters"]["oracle_fallbacks"] == 0
    assert res["attempted"] > 0 and res["failed"] == 0
    if trace:
        assert "fixpoint_rounds" in res["metrics"]
    else:
        assert set(res["metrics"]) == {"sim_req_per_s", "setup_s"}


def _patched_fixpoint(fault):
    """The engine's fixpoint with ``fault`` applied to what it returns."""
    import jax
    import jax.numpy as jnp

    from repro.core import engine

    orig = engine._simulate_fixpoint

    @functools.partial(jax.jit, static_argnames=("impl",))
    def broken(hops, channels, issue_ps, ser0, rounds, carry, impl):
        n = hops.channel.shape[0]
        if fault == "unchanged":
            # the iteration returns the state it was handed: no rounds run
            s = orig(hops, channels, issue_ps, ser0, jnp.int64(0), carry,
                     impl=impl)
            return s._replace(converged=jnp.bool_(True),
                              residual_ps=jnp.int64(0))
        if fault == "half":
            # half of the requests left out of the fixpoint
            keep = (jnp.arange(n) < n // 2)[:, None]
            hops = hops._replace(valid=hops.valid & keep)
            return orig(hops, channels, issue_ps, ser0, rounds, carry,
                        impl=impl)
        s = orig(hops, channels, issue_ps, ser0, rounds, carry, impl=impl)
        # one answer altered where it is produced
        return s._replace(depart=s.depart.at[0, 1].add(1))
    return broken


@pytest.mark.parametrize("cell", sorted(TINY))
@pytest.mark.parametrize("fault", ["unchanged", "half", "altered"])
def test_broken_timed_path_is_not_correct(bench, cell, fault, monkeypatch):
    from repro.core import engine

    monkeypatch.setattr(engine, "_simulate_fixpoint",
                        _patched_fixpoint(fault))
    res = run_cell(bench, cell)
    assert res["correct"] is False
    assert max(c["value"] for c in res["checks"].values()) > 0


def test_stream_missing_windows_is_not_correct(bench, monkeypatch):
    import repro.core as C

    orig = C.simulate_stream

    def every_other_chunk(chunks, *a, **kw):
        return orig((ck for i, ck in enumerate(chunks) if i % 2 == 0),
                    *a, **kw)
    monkeypatch.setattr(C, "simulate_stream", every_other_chunk)
    res = run_cell(bench, "rack16.stream")
    assert res["correct"] is False


@pytest.mark.parametrize("cell,kind", sorted(CONTROL))
def test_control_fails_the_comparison(bench, cell, kind):
    from repro.core import SimOptions

    _, cfg, traffic = harness.cell_files(bench, cell)
    traffic = dict(traffic, **CONTROL[cell, kind])
    work = registry.load("units", traffic["unit"]).Unit(
        cfg, traffic, SEED, SimOptions(use_kernel="interpret"),
        harness.Spans())
    counts = work.control(0, kind)
    assert max(counts.values()) > 0, counts


@pytest.mark.parametrize("cell", sorted(TINY))
def test_reference_in_the_programs_place_is_correct(bench, cell,
                                                    monkeypatch):
    """The control path itself compares like with like: the exact
    reference in the program's place reads 0 everywhere."""
    from repro.core import SimOptions

    _, cfg, traffic = harness.cell_files(bench, cell)
    traffic = dict(traffic, **TINY[cell])
    from chipbench.reference import des

    monkeypatch.setitem(des.CONTROLS, "exact", {})
    work = registry.load("units", traffic["unit"]).Unit(
        cfg, traffic, SEED, SimOptions(use_kernel="interpret"),
        harness.Spans())
    assert set(work.control(0, "exact").values()) == {0}


def test_measuring_path_refuses_the_cpu(capsys):
    rc = harness.main(["--workload", "rack16.mono", "--seed", "1",
                       "--seconds", "1", "--trace", "0"])
    assert rc == 3
    assert capsys.readouterr().out == ""
