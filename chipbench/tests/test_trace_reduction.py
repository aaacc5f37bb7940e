"""The reduction from a profiler trace to the per-layer metrics: interval
arithmetic on a hand-made trace whose answers are known, and the readers
on a small trace recorded on a TPU v5e (`data/`)."""

import gzip
import json
import os

import pytest

from chipbench import run as harness
from chipbench import trace as tr

DATA = os.path.join(os.path.dirname(__file__), "data")
PEAK = {"hbm_bytes_per_s": 819e9}      # TPU v5 lite, peaks.json


def toy():
    # window [0, 1000); ops on one device: a loop [95, 705) holding
    # [100, 300) and [250, 400), which overlap, and the kernel [600, 700);
    # spans: simulate_stream [0, 500) and [500, 1000)
    return {
        "spans": [["window", 0, 1000], ["simulate_stream", 0, 500],
                  ["simulate_stream", 500, 500], ["build_workload", 450, 100]],
        "devices": [{
            "name": "/device:TPU:0", "lines": ["XLA Modules", "XLA Ops"],
            "ops": [["while.3", "jit__simulate_fixpoint", 95, 610],
                    ["fusion.1", "jit__simulate_fixpoint", 100, 200],
                    ["sort.2", "jit__simulate_fixpoint", 250, 150],
                    ["serve_scan.9", "jit__simulate_fixpoint", 600, 100],
                    ["late", "jit_other", 1200, 50]],
            "modules": [["jit__simulate_fixpoint", 90, 620],
                        ["jit_attribute_latency", 900, 50]],
        }],
    }


def test_busy_union_and_idle():
    t = toy()
    assert tr.union([(5, 9), (0, 3), (2, 4)]) == [(0, 4), (5, 9)]
    assert tr.busy_ns(t) == 610                 # the loop, 95..705
    assert tr.idle_in(t, "simulate_stream") == 390
    t["devices"][0]["ops"].pop(0)               # without the loop
    assert tr.busy_ns(t) == 400                 # 100..400 and 600..700
    assert tr.idle_in(t, "simulate_stream") == 600
    assert harness.reader("device_idle_share")(
        {"trace": t, "counters": {}}) == pytest.approx(60.0)
    assert harness.reader("stream_idle_ms_per_window")(
        {"trace": t, "counters": {"windows": 3}}) == pytest.approx(2e-4)


def test_kernel_time_and_roofline_arithmetic():
    t = toy()
    assert tr.op_ns(t, lambda op, prog: op.startswith("serve_scan")) == 100
    read = harness.reader("serve_round_roofline")
    items = 1000
    got = read({"trace": t, "counters": {"serve_items": items},
                "peak": PEAK})
    want = 100 * (28 * items / 819e9) / 100e-9
    assert got == pytest.approx(want)
    # no kernel in the trace: nothing to read, never 0
    t["devices"][0]["ops"].pop(3)
    assert read({"trace": t, "counters": {"serve_items": items},
                 "peak": PEAK}) is None


def test_program_time_per_round_and_per_study():
    t = toy()
    assert harness.reader("round_device_ms")(
        {"trace": t, "counters": {"rounds": 2}}) == pytest.approx(310e-6)
    assert harness.reader("telemetry_device_ms")(
        {"trace": t, "counters": {"units": 1}}) == pytest.approx(50e-6)


def test_breakdown_names_gaps_by_span():
    t = toy()
    t["devices"][0]["ops"].pop(0)
    b = tr.breakdown(t)
    assert b["device_ops"][0] == ["jit__simulate_fixpoint/fusion.1", 2e-7]
    assert not any(k.endswith("while.3") for k, _ in tr.breakdown(toy())[
        "device_ops"])
    gaps = dict((round(s * 1e9), n) for n, s in b["idle_gaps"])
    assert gaps[300] == "simulate_stream"       # 700..1000
    assert gaps[200] == "build_workload"        # 400..600, mid at 500


@pytest.mark.parametrize("cell", ["rack16.mono", "rack16.stream"])
def test_recorded_trace_reads(cell):
    """A one-unit trace recorded on a TPU v5e (``run.py --trace 1
    --trace-out``): every per-layer metric of the cell reads again to the
    value the run reported, and stays a sane share."""
    with gzip.open(os.path.join(DATA, f"{cell}.json.gz"), "rt") as f:
        rec = json.load(f)
    t = rec["trace"]
    lo, hi = tr.window(t)
    assert 0 < tr.busy_ns(t) < hi - lo
    assert tr.busy_ns(t) / 1e9 == pytest.approx(rec["device"]["busy_s"])
    bench = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    c = {w["name"]: w for w in bench["workloads"]}[cell]
    rec["peak"] = harness.load_json(os.path.join(
        harness.HERE, "peaks.json"))["devices"][rec["device"]["kind"]]
    names = [m["name"] for m in harness.metrics_of(bench, c, "per_layer")]
    assert sorted(names) == sorted(rec["metrics"])
    for m in harness.metrics_of(bench, c, "per_layer"):
        v = harness.reader(m["name"])(rec)
        assert v == pytest.approx(rec["metrics"][m["name"]], rel=1e-12)
        assert v > 0
        if m["unit"] == "%":
            assert v <= 100, m["name"]
