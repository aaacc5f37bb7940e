#!/usr/bin/env python3
"""Run one benchmark cell once on the chip this process holds.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

from the root of a checkout.  Everything is found by name:
`BENCHMARK.json` maps the cell to its configuration file and its traffic
(``chipbench/traffic/<traffic>.json``, whose ``"unit"`` names the unit of
work, ``chipbench/units/<unit>.py``); each per-layer metric is read by
``chipbench/metrics/<metric>.py`` (see `registry`).

Set-up (counted in ``setup_s``, from the start of this process): build the
cell's inputs from the seed, compile the cell's programs in parallel
threads, warm up every program and shape the window will use.  The
window then repeats whole units until ``--seconds`` have passed; the
rate is every request of every unit over all that time, and backend
compiles inside the window are counted.
``--trace 1`` wraps the window in the JAX profiler and reports the per-layer
metrics instead of the end-to-end ones.  After the window, one unit drawn
from the seed is checked against the plain reference (`reference/`); every
number compared is printed beside its limit, last on standard error and
last in the result line.  The result is one JSON line, last on standard
output.

The first device must be a TPU and there must be as many as the cell asks
for; otherwise the run exits 3 and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from concurrent.futures import ThreadPoolExecutor  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "chipbench")
# the checkout root first (for the chipbench package), then the program
if sys.path and os.path.abspath(sys.path[0]) == HERE:
    sys.path.pop(0)
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
# libtpu would otherwise log to a fixed directory under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
# the harness's own spans; a unit adds its own (its module's ``SPANS``)
BASE_SPANS = ("window", "unit")
# set-up before a run starts: importing JAX, and finding the chip
START_PHASES: dict = {}


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def cell_files(bench: dict, workload: str) -> tuple[dict, dict, dict]:
    """The cell's entry, its configuration and its traffic."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; have "
                         f"{sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg = load_json(os.path.join(ROOT, configs[cell["config"]]["file"]))
    traffic = load_json(os.path.join(HERE, "traffic",
                                     cell["traffic"] + ".json"))
    return cell, cfg, traffic


def metrics_of(bench: dict, cell: dict, kind: str) -> list[dict]:
    """The cell's end-to-end or per-layer metrics (a metric with a
    ``workloads`` list belongs to those cells only; a per-layer metric
    without one belongs to every cell that reports the metric it moves)."""
    e2e = [m for m in bench["end_to_end"]
           if cell["name"] in m.get("workloads", [cell["name"]])]
    if kind == "end_to_end":
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if cell["name"] in m.get("workloads", [cell["name"]])
            and m["moves"] in names]


def reader(name: str):
    from chipbench import registry

    return registry.load("metrics", name).read


def reservoir(seed: int):
    """Uniform sample of one unit out of however many the window runs:
    keep the k-th unit with probability 1/k (draws from the seed)."""
    from chipbench.generator import rng_for

    rng = rng_for(seed, 0x5A)

    def keep(k: int) -> bool:
        return k == 1 or rng.random() < 1.0 / k
    return keep


class Spans:
    """Host spans around the harness's calls into the program: kept in
    memory on the host clock, and written into the profiler's trace."""

    def __init__(self):
        import jax

        self.annotation = jax.profiler.TraceAnnotation
        self.records: list[list] = []

    @contextlib.contextmanager
    def __call__(self, name: str):
        t0 = time.perf_counter_ns()
        with self.annotation(name):
            yield
        self.records.append([name, t0, time.perf_counter_ns() - t0])


class CompileCount:
    def __init__(self):
        self.n = 0
        self.seconds = 0.0

    def __call__(self, event: str, seconds: float, **_) -> None:
        if event == _BACKEND_COMPILE:
            self.n += 1
            self.seconds += seconds


def compile_all(jobs: dict) -> dict:
    """Compile every program in ``jobs`` (name -> (jitted fn, *args)) in
    parallel threads; the compiler releases the GIL."""
    def build(job):
        fn, *args = job
        t0 = time.perf_counter()
        fn.lower(*args).compile()
        return time.perf_counter() - t0

    with ThreadPoolExecutor(max(len(jobs), 1)) as pool:
        futs = {k: pool.submit(build, j) for k, j in jobs.items()}
        return {k: f.result() for k, f in futs.items()}


def run(bench: dict, workload: str, seed: int, seconds: float, trace: bool,
        options=None, peaks: dict | None = None, cache: bool = True,
        sizes: dict | None = None, trace_out: str | None = None) -> dict:
    """One run of one cell on the process's first device; returns the
    result line as a dict.  ``options`` defaults to
    ``SimOptions(use_kernel=True)``.  ``sizes`` overrides traffic keys
    (the CPU rehearsal runs a cell at a tiny size).  ``trace_out`` keeps
    the compact trace of a traced run, with what was read from it, as
    gzipped JSON there."""
    import jax

    from chipbench import registry
    from chipbench import trace as tr
    from repro.compile_cache import use_compile_cache
    from repro.core import SimOptions

    phases = dict(START_PHASES,
                  imports=time.perf_counter() - T_START
                  - sum(START_PHASES.values()))
    cell, cfg, traffic = cell_files(bench, workload)
    traffic = dict(traffic, **(sizes or {}))
    if cache:
        use_compile_cache(ROOT)
        # cache every program, however quick to build, so that a warm
        # set-up compiles nothing
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles = CompileCount()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    dev = jax.devices()[0]
    if peaks is None:
        peaks = load_json(os.path.join(HERE, "peaks.json"))["devices"]
    peak = peaks[dev.device_kind] if trace else None

    options = options or SimOptions(use_kernel=True)
    spans = Spans()
    unit_mod = registry.load("units", traffic["unit"])
    t = time.perf_counter()
    work = unit_mod.Unit(cfg, traffic, seed, options, spans)
    phases["inputs"] = time.perf_counter() - t
    t = time.perf_counter()
    compile_s = compile_all(work.compile_jobs())
    phases["compile"] = time.perf_counter() - t
    t = time.perf_counter()
    work.warm_up()
    phases["warm_up"] = time.perf_counter() - t
    for k in work.counters:
        work.counters[k] = 0
    spans.records.clear()
    setup_s = time.perf_counter() - T_START

    keep = reservoir(seed)
    sample = None
    with contextlib.ExitStack() as stack:
        logdir = None
        if trace:
            logdir = stack.enter_context(tempfile.TemporaryDirectory())
            jax.profiler.start_trace(logdir,
                                     profiler_options=tr.options())
        n_compiles = compiles.n
        with spans("window"):
            t0 = time.perf_counter()
            k = 0
            while True:
                k += 1
                with spans("unit"):
                    out = work.run_unit(k)
                if keep(k):
                    sample = out
                del out
                if time.perf_counter() - t0 >= seconds:
                    break
            elapsed = time.perf_counter() - t0
        window_compiles = compiles.n - n_compiles
        compact = None
        if trace:
            jax.profiler.stop_trace()
            compact = tr.compact(logdir, BASE_SPANS + unit_mod.SPANS)
    stats = dev.memory_stats() or {}
    counters = dict(work.counters, window_compiles=window_compiles,
                    window_s=elapsed, units=k)

    checks = work.check(sample)
    limits = {name: 0 for name in checks}        # exact comparisons
    correct = all(checks[n] <= limits[n] for n in checks)

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}
    metrics = {}
    extra = {}
    if not trace:
        values = {"sim_req_per_s": counters["requests"] / elapsed,
                  "setup_s": setup_s}
        for m in metrics_of(bench, cell, "end_to_end"):
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    else:
        rec = {"cell": workload, "counters": counters,
               "spans": spans.records, "trace": compact, "peak": peak}
        for m in metrics_of(bench, cell, "per_layer"):
            v = reader(m["name"])(rec)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        lo, hi = tr.window(compact)
        device["busy_s"] = tr.busy_ns(compact) / 1e9
        device["window_s"] = (hi - lo) / 1e9
        extra["breakdown"] = tr.breakdown(compact)
        if trace_out:
            kept = {k: rec[k] for k in ("cell", "counters", "spans",
                                        "trace")}
            kept["metrics"] = {k: v["value"] for k, v in metrics.items()}
            kept["device"] = {k: device[k] for k in ("kind", "busy_s",
                                                     "window_s")}
            with gzip.open(trace_out, "wt") as f:
                json.dump(kept, f)
    result = {"correct": correct, "attempted": counters["requests"],
              "failed": counters["fallback_requests"], "metrics": metrics,
              "device": device, **extra,
              "counters": dict(counters, setup_s=setup_s,
                               setup_phases=phases, compile_s=compile_s,
                               setup_compiles=n_compiles,
                               setup_compile_s=compiles.seconds),
              "checks": {n: {"value": checks[n], "limit": limits[n]}
                         for n in checks}}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-out", default=None,
                    help="with --trace 1, also keep the compact trace, the "
                    "counters, the spans and the metrics read from them as "
                    "gzipped JSON here (how tests/data/ was recorded)")
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative integer")

    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell, _, _ = cell_files(bench, args.workload)

    t = time.perf_counter()
    import jax

    START_PHASES["import_jax"] = time.perf_counter() - t
    t = time.perf_counter()
    devs = jax.devices()
    START_PHASES["devices"] = time.perf_counter() - t
    if devs[0].platform != "tpu" or len(devs) < int(cell["chips"]):
        print(f"chipbench: needs {cell['chips']} TPU chip(s); JAX has "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        return 3
    result = run(bench, args.workload, args.seed, args.seconds,
                 bool(args.trace), trace_out=args.trace_out)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
