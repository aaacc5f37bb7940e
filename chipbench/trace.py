"""From a JAX profiler trace to the few lists the metric readers need.

`compact` reads the ``.xplane.pb`` that `jax.profiler` writes and keeps:

  * ``spans``: the harness's own host spans (`jax.profiler.TraceAnnotation`
    events with the names it is given), as ``[name, start_ns, dur_ns]``;
  * ``devices``: per device plane, the events of its ``XLA Ops`` line
    (``[op, program, start_ns, dur_ns]``, the op by its HLO instruction
    name) and of its ``XLA Modules`` line (``[program, start_ns,
    dur_ns]``).

Host and device events share the trace's clock.  The reductions below work
on that compact form only, so they can be checked on a small recorded trace
(`tests/data/`) without a chip.
"""

from __future__ import annotations

import glob
import os

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# operations that contain others on the ops line (a loop, a branch): their
# time is their body's, so the top-operations list leaves them out
CONTAINERS = ("while", "conditional", "call")


def options():
    """Profiler options for the traced window: no Python function tracer
    (it slows the host's Python, and the lowering layer is Python), host
    annotations kept."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts


def _op_name(text: str) -> str:
    """``%fusion.12 = (...) fusion(...)`` -> ``fusion.12``."""
    return text.split(" = ", 1)[0].lstrip("%")


def _program(name: str) -> str:
    """``jit__simulate_fixpoint(123)`` -> ``jit__simulate_fixpoint``."""
    return name.split("(", 1)[0]


def compact(logdir: str, span_names) -> dict:
    """The compact form of the one ``.xplane.pb`` under ``logdir``, with
    the host spans called one of ``span_names``.  Each operation is named
    by its HLO instruction and by the program whose execution encloses it
    on the device's ``XLA Modules`` line."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one xplane file under {logdir}, "
                           f"found {len(paths)}")
    pd = ProfileData.from_file(paths[0])
    spans, devices = [], []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in span_names:
                        spans.append([e.name, int(e.start_ns),
                                      int(e.duration_ns)])
        elif plane.name.startswith("/device:"):
            ops, modules = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = [(_op_name(e.name), int(e.start_ns),
                            int(e.duration_ns)) for e in line.events]
                elif line.name == MODULES_LINE:
                    modules = [[_program(e.name), int(e.start_ns),
                                int(e.duration_ns)] for e in line.events]
            if ops or modules:
                devices.append({
                    "name": plane.name,
                    "lines": sorted({line.name for line in plane.lines}),
                    "ops": _attribute(ops, modules), "modules": modules})
    spans.sort(key=lambda s: s[1])
    return {"spans": spans, "devices": devices}


def _attribute(ops, modules) -> list[list]:
    """``[op, program, start, dur]``: each operation with the program
    execution that encloses its start ("" where none does)."""
    mods = sorted(modules, key=lambda m: m[1])
    out, j = [], 0
    for op, s, d in sorted(ops, key=lambda o: o[1]):
        while j < len(mods) and mods[j][1] + mods[j][2] <= s:
            j += 1
        prog = mods[j][0] if j < len(mods) and mods[j][1] <= s else ""
        out.append([op, prog, s, d])
    return out


def union(intervals) -> list[tuple[int, int]]:
    """Merged, sorted ``(start, end)`` intervals."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def covered(intervals, lo: int, hi: int) -> int:
    """Nanoseconds of ``[lo, hi)`` that merged ``intervals`` cover."""
    return sum(e - s for s, e in clip(intervals, lo, hi))


def spans_named(trace: dict, name: str) -> list[tuple[int, int]]:
    return [(s, s + d) for n, s, d in trace["spans"] if n == name]


def window(trace: dict) -> tuple[int, int]:
    """The measured window: the harness's ``window`` span."""
    w = spans_named(trace, "window")
    if len(w) != 1:
        raise ValueError(f"expected one window span, found {len(w)}")
    return w[0]


def busy(dev: dict) -> list[tuple[int, int]]:
    """Intervals in which an operation ran on one device."""
    return union((s, s + d) for _, _, s, d in dev["ops"])


def busy_ns(trace: dict) -> float:
    """Busy nanoseconds inside the window, averaged over the devices."""
    lo, hi = window(trace)
    devs = trace["devices"]
    if not devs:
        return 0.0
    return sum(covered(busy(d), lo, hi) for d in devs) / len(devs)


def idle_in(trace: dict, name: str) -> float:
    """Device-idle nanoseconds inside the host spans called ``name`` (and
    inside the window), averaged over the devices."""
    lo, hi = window(trace)
    spans = union(clip(spans_named(trace, name), lo, hi))
    devs = trace["devices"]
    if not devs or not spans:
        return 0.0
    total = 0
    for d in devs:
        b = busy(d)
        total += sum((e - s) - covered(b, s, e) for s, e in spans)
    return total / len(devs)


def op_ns(trace: dict, match) -> float:
    """Device nanoseconds (summed over devices, inside the window) of the
    operations for which ``match(op, program)`` holds."""
    lo, hi = window(trace)
    return float(sum(
        covered([(s, s + d)], lo, hi)
        for dev in trace["devices"] for op, prog, s, d in dev["ops"]
        if match(op, prog)))


def module_ns(trace: dict, match) -> float:
    """Device nanoseconds (summed over devices, inside the window) of the
    program executions whose name satisfies ``match``."""
    lo, hi = window(trace)
    return float(sum(
        covered([(s, s + d)], lo, hi)
        for dev in trace["devices"] for name, s, d in dev["modules"]
        if match(name)))


def breakdown(trace: dict, top: int = 10) -> dict:
    """The device operations that took most time (by program and op name)
    and the longest device-idle gaps inside the window, each named by the
    innermost harness span that covers the gap's midpoint."""
    lo, hi = window(trace)
    per_op: dict[str, float] = {}
    for dev in trace["devices"]:
        for op, prog, s, d in dev["ops"]:
            if op.split(".")[0] in CONTAINERS:
                continue
            t = covered([(s, s + d)], lo, hi)
            if t:
                key = f"{prog}/{op}" if prog else op
                per_op[key] = per_op.get(key, 0) + t
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]

    gaps = []                       # (length, start) on the first device
    for dev in trace["devices"][:1]:
        prev = lo
        for s, e in clip(busy(dev), lo, hi) + [(hi, hi)]:
            if s > prev:
                gaps.append((s - prev, prev))
            prev = max(prev, e)
    gaps = sorted(gaps, reverse=True)[:top]
    inner = [s for s in trace["spans"] if s[0] != "window"]
    named = []
    for length, start in gaps:
        mid = start + length // 2
        cover = [sp for sp in inner if sp[1] <= mid < sp[1] + sp[2]]
        name = (min(cover, key=lambda sp: sp[2])[0] if cover
                else "between spans")
        named.append([name, length / 1e9])
    return {"device_ops": [[k, v / 1e9] for k, v in ops],
            "idle_gaps": named}
