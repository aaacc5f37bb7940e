"""Benchmark driver: one module per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run [--quick] [--only fig10,...]
[--json BENCH_smoke.json]``

Prints ``name,us_per_call,derived`` CSV.  ``derived`` carries the reproduced
quantity and the paper target it validates against (see DESIGN.md §7 for the
experiment index).  Framework-level benches (fabric collective model, kernels,
autotune) live alongside the paper-figure benches.

``--json`` additionally writes the rows as a machine-readable snapshot —
CI uploads these as ``BENCH_*.json`` workflow artifacts on every run, so the
repo accumulates a perf trajectory without committing result files.  Any
bench whose embedded acceptance gate fails (AssertionError in its ``run()``)
exits nonzero, failing the CI job.
"""

from __future__ import annotations

import argparse
import json
import platform
import sys
import time

MODULES = (
    ("validation", "benchmarks.bench_validation"),
    ("topology", "benchmarks.bench_topology"),
    ("routing", "benchmarks.bench_routing"),
    ("snoop_filter", "benchmarks.bench_snoop_filter"),
    ("invblk", "benchmarks.bench_invblk"),
    ("full_duplex", "benchmarks.bench_full_duplex"),
    ("link_layer", "benchmarks.bench_link_layer"),
    ("link_reliability", "benchmarks.bench_link_reliability"),
    ("coherence_fabric", "benchmarks.bench_coherence_fabric"),
    ("telemetry", "benchmarks.bench_telemetry"),
    ("critical_path", "benchmarks.bench_critical_path"),
    ("streaming", "benchmarks.bench_streaming"),
    ("traces", "benchmarks.bench_traces"),
    ("coherence_modes", "benchmarks.bench_coherence_modes"),
    ("fabric", "benchmarks.bench_fabric"),
    ("kernels", "benchmarks.bench_kernels"),
)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced sizes (CI-friendly)")
    ap.add_argument("--only", type=str, default="",
                    help="comma-separated bench names")
    ap.add_argument("--json", type=str, default="",
                    help="also write results to this JSON file "
                         "(CI perf-trajectory artifact)")
    args = ap.parse_args()
    only = {s.strip() for s in args.only.split(",") if s.strip()}

    import importlib
    import os

    from repro.compile_cache import use_compile_cache

    use_compile_cache(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    t0 = time.time()
    failed: list[str] = []
    errors: dict[str, str] = {}
    results: list[dict] = []
    unknown = only - {name for name, _ in MODULES}
    if unknown:
        # a typo in --only must not silently skip an acceptance gate
        print(f"unknown bench names: {sorted(unknown)}", file=sys.stderr)
        sys.exit(2)
    print("name,us_per_call,derived")
    for name, modname in MODULES:
        if only and name not in only:
            continue
        t_imp = time.perf_counter()
        try:
            mod = importlib.import_module(modname)
        except ImportError as e:  # pragma: no cover
            print(f"{name}/import_error,0.0,{e}")
            failed.append(name)
            errors[name] = f"ImportError:{e}"
            continue
        import_s = time.perf_counter() - t_imp
        t_run = time.perf_counter()
        try:
            rows = mod.run(quick=args.quick)
        except Exception as e:
            print(f"{name}/run_error,0.0,{type(e).__name__}:{e}")
            failed.append(name)
            errors[name] = f"{type(e).__name__}:{e}"
            continue
        run_s = time.perf_counter() - t_run
        for r in rows:
            print(r.csv())
            sys.stdout.flush()
            row = {"name": r.name, "us_per_call": r.us_per_call,
                   "derived": r.derived}
            # convergence/telemetry counters + host-side phase wall-clock
            # (build/lower/compile/execute when the bench reports them;
            # whole-module import/run always)
            meta = dict(r.meta) if getattr(r, "meta", None) else {}
            phases = dict(meta.get("host_phases", {}))
            phases.setdefault("import_s", round(import_s, 6))
            phases.setdefault("run_s", round(run_s, 6))
            meta["host_phases"] = phases
            row["meta"] = meta
            results.append(row)
    wall_s = time.time() - t0
    print(f"total_wall_s,{wall_s:.1f},")
    if args.json:
        import jax

        snapshot = {
            "quick": args.quick,
            "only": sorted(only),
            "wall_s": round(wall_s, 1),
            "python": platform.python_version(),
            "jax": jax.__version__,
            "platform": platform.platform(),
            "rows": results,
            "failed": sorted(failed),
            "errors": errors,
        }
        with open(args.json, "w") as f:
            json.dump(snapshot, f, indent=1, sort_keys=True)
        print(f"wrote {args.json} ({len(results)} rows)", file=sys.stderr)
    if failed:
        # embedded acceptance gates (AssertionErrors in bench run()) must
        # fail the CI smoke step, not just print a run_error row
        print(f"failed,{len(failed)},{';'.join(failed)}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
