"""The simulator's spans and scopes (`core.spans`): a lowering and a
two-window stream under the profiler emit exactly the exported host spans,
one per phase per call, and the compiled fixpoint carries every round
scope in its operations' metadata."""

import collections
import glob
import os
import re

import jax
import jax.numpy as jnp
import pytest

import repro.core  # noqa: F401  (x64)
from repro.core import topology as T
from repro.core.devices import RequesterSpec, build_workload
from repro.core.engine import _simulate_fixpoint, empty_carry, hop_ser_ps
from repro.core.spans import NAMES
from repro.core.streaming import simulate_stream, stream_windows

HOST = tuple(n for n in NAMES if not n.startswith("round."))
SCOPES = tuple(n for n in NAMES if n.startswith("round."))


def _workload():
    g = T.spine_leaf(2, n_spines=2, per_leaf=2).build()
    specs = [RequesterSpec(node=r, n_requests=8, targets=[m])
             for r, m in zip(g.topo.requesters(), g.topo.memories())]
    return build_workload(g, specs, warmup_frac=0.0)


def _host_events(logdir) -> collections.Counter:
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    assert len(paths) == 1, paths
    pd = ProfileData.from_file(paths[0])
    return collections.Counter(
        e.name for plane in pd.planes if plane.name.startswith("/host:")
        for line in plane.lines for e in line.events
        if e.name.split(".")[0] in ("lower", "window", "round"))


def test_names_are_unique_and_grouped():
    assert len(set(NAMES)) == len(NAMES)
    assert {n.split(".")[0] for n in NAMES} == {"lower", "window", "round"}


def test_host_spans_one_per_phase_per_call(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        wl = _workload()
        chunks = list(stream_windows(wl.hops, wl.issue_ps,
                                     wl.hops.channel.shape[0] // 2))
        assert len(chunks) == 2
        res = simulate_stream(chunks, wl.channels, pad_to=8)
        jax.block_until_ready(res.telemetry)
    assert res.windows == 2
    got = _host_events(str(tmp_path))
    want = {n: 1 if n.startswith("lower.") else 2 for n in HOST}
    assert dict(got) == want


def _op_names(text: str) -> list[tuple[str, str]]:
    """``(opcode, op_name)`` of each instruction of an HLO text."""
    out = []
    for line in text.splitlines():
        m = re.search(r'op_name="([^"]*)"', line)
        op = re.search(r"=\s*(?:\([^=]*\)|\S+)\s+([a-z][\w-]*)\(", line)
        if m and op:
            out.append((op.group(1), m.group(1)))
    return out


@pytest.mark.parametrize("impl,with_carry", [("scan", False),
                                              ("scan", True),
                                              ("ref", True)])
def test_round_scopes_in_compiled_fixpoint(impl, with_carry):
    wl = _workload()
    ser = hop_ser_ps(wl.hops, wl.channels)
    carry = (empty_carry(int(wl.channels.bw_MBps.shape[0]))
             if with_carry else None)
    text = _simulate_fixpoint.lower(wl.hops, wl.channels, wl.issue_ps, ser,
                                    jnp.int64(4), carry,
                                    impl=impl).compile().as_text()
    ops = _op_names(text)
    for scope in SCOPES:
        assert any(f"/{scope}/" in name for _, name in ops), scope
    # the sorts are the order step's and the scatter step's un-permuting
    # sort, and this join-free program holds no scatter op at all
    sorts = [name for op, name in ops if op == "sort"]
    assert all("/round.order/" in n or "/round.scatter/" in n for n in sorts)
    assert any("/round.order/" in n for n in sorts)
    assert any("/round.scatter/" in n for n in sorts)
    assert not re.search(r"\sscatter\(", text)
    # and nothing in the compiled program is named after a host span
    assert not any(n in text for n in HOST)

