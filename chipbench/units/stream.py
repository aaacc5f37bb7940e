"""A unit of work that is one replay of a streamed trace: the rack's
requests, lowered once in set-up into chunks that follow one another
without a gap, go through the windowed engine; the stream's summary and
its latency histogram are the output.

    simulate_stream (window assembly, fixpoint, settlement, fold)
    -> StreamResult.summary()

Rows still in flight at a window's edge are carried into the next window.
Checked against the reference run over the whole replay at once: the
per-channel counters, the totals and span, and the latency histogram with
its quantiles, bin for bin.
"""

from __future__ import annotations

import numpy as np

from chipbench import compare, generator
from chipbench.fabric import Fabric
from chipbench.reference import des
from chipbench.reference import telemetry as reftel
from chipbench.reference.lowering import lower

SPANS = ("simulate_stream", "stream_chunk", "summary")

_FIELDS = ("channel", "nbytes", "direction", "row", "fixed_after_ps",
           "is_payload", "valid", "extra_wire_bytes", "retrain_after_ps")
# the types the engine's stream windows carry (`streaming._process_window`)
_WINDOW_DTYPES = {"channel": "int32", "nbytes": "int64", "direction": "int8",
                  "row": "int32", "fixed_after_ps": "int64",
                  "is_payload": "bool", "valid": "bool",
                  "extra_wire_bytes": "int64", "retrain_after_ps": "int64"}


class Unit:
    def __init__(self, cfg, traffic, seed, options, span):
        import jax

        self.jax, self.span = jax, span
        self.cfg, self.options = cfg, options
        self.fabric = Fabric(cfg)
        n_hosts = len(self.fabric.hosts)
        self.requests = [generator.chunk(traffic, n_hosts, seed, 0, i)
                         for i in range(int(traffic["chunks"]))]
        self.chunks = []
        for ck in self.requests:
            wl = self.fabric.lower(ck)
            hops = wl.hops._replace(**{
                f: np.asarray(getattr(wl.hops, f)) for f in _FIELDS
                if getattr(wl.hops, f) is not None})
            self.chunks.append((hops, np.asarray(wl.issue_ps)))
            self.channels = wl.channels
        self.window_rows = int(traffic["window_rows"])
        self.counters = {"units": 0, "requests": 0, "fixpoint_calls": 0,
                         "rounds": 0, "serve_items": 0,
                         "oracle_fallbacks": 0, "unconverged": 0,
                         "fallback_requests": 0, "windows": 0,
                         "carried_peak": 0}

    def compile_jobs(self) -> dict:
        from repro.core import empty_carry
        from repro.core.engine import _simulate_fixpoint, hop_ser_ps

        jax, jnp = self.jax, self.jax.numpy
        hops, _ = self.chunks[0]
        shape = (self.window_rows, hops.channel.shape[1])
        hops = hops._replace(**{
            f: jax.ShapeDtypeStruct(shape, jnp.dtype(_WINDOW_DTYPES[f]))
            for f in _FIELDS if getattr(hops, f) is not None})
        issue = jax.ShapeDtypeStruct(shape[:1], jnp.int64)
        ser = jax.ShapeDtypeStruct(shape, jnp.int64)
        carry = empty_carry(int(self.channels.bw_MBps.shape[0]))
        return {
            "fixpoint": (_simulate_fixpoint, hops, self.channels, issue, ser,
                         jnp.int64(1), carry, self.options.kernel_impl),
            "serialization": (hop_ser_ps, hops, self.channels),
        }

    def _chunks(self, chunks):
        for ck in chunks:
            with self.span("stream_chunk"):
                yield ck

    def _replay(self, chunks):
        with self.span("simulate_stream"):
            res = self.fabric.C.simulate_stream(
                self._chunks(chunks), self.channels, options=self.options,
                pad_to=self.window_rows)
        with self.span("summary"):
            summary = res.summary()
        return res, summary

    def warm_up(self):
        """Every program and shape of a replay, from its first two chunks:
        a window that carries rows out, then one that takes them in and
        drains (every window of a replay has the same shape)."""
        self._replay(self.chunks[:2])

    def run_unit(self, unit: int):
        res, summary = self._replay(self.chunks)
        sk = res.telemetry.sketch
        hist = {"counts": np.asarray(sk.counts), "n": int(sk.n),
                "min_ps": int(sk.min_ps), "max_ps": int(sk.max_ps)}
        h = self.chunks[0][0].channel.shape[1]
        c = self.counters
        c["units"] += 1
        c["requests"] += res.n_rows
        c["windows"] += res.windows
        c["fixpoint_calls"] += res.windows
        c["rounds"] += res.rounds
        c["serve_items"] += res.rounds * self.window_rows * h
        c["oracle_fallbacks"] += res.oracle_windows
        c["fallback_requests"] += res.oracle_windows * self.window_rows
        c["unconverged"] += res.windows - res.state.windows_converged
        c["carried_peak"] = max(c["carried_peak"], res.carried_peak)
        return {"unit": unit, "summary": summary, "histogram": hist}

    def reference(self, **how):
        reqs = [generator.flat(ck) for ck in self.requests]
        tab = lower(self.cfg, {k: np.concatenate([r[k] for r in reqs])
                               for k in reqs[0]})
        ref = des.simulate(tab["channel"], tab["ser"], tab["direction"],
                           tab["row"], tab["fixed"], tab["valid"],
                           tab["issue"], tab["turnaround"], tab["row_hit"],
                           tab["row_miss"], **how)
        return tab, ref

    def check(self, out) -> dict:
        prog_chan = np.concatenate([h.channel for h, _ in self.chunks])
        return self.compare(out["summary"], out["histogram"], prog_chan)

    def control(self, unit: int, kind: str) -> dict:
        """The same counts with the reference itself in the program's
        place, computed as `des.CONTROLS` ``kind`` says."""
        tab, r = self.reference(**des.CONTROLS[kind])
        summary, hist = self._summary(tab, r)
        return self.compare(summary, hist, tab["channel"])

    @staticmethod
    def _summary(tab, ref):
        """What a stream reports, worked out from a reference schedule."""
        cc = reftel.channel_counters(tab, ref, tab["n_channels"])
        hist = reftel.latency_histogram(ref["complete"] - tab["issue"])
        summary = dict(cc, n_retired=len(tab["issue"]),
                       span_ps=reftel.span(tab, ref),
                       quantiles_ps=reftel.quantiles(hist),
                       blame={"row_extra_ps": cc["row_extra_ps"],
                              "fixed_ps": _fixed_sum(tab), "join_ps": 0})
        return summary, hist

    def compare(self, s: dict, hist: dict, prog_chan) -> dict:
        """Mismatches between a stream's summary and histogram, whose
        channels are numbered as in the hop tables ``prog_chan``, and the
        reference's."""
        tab, ref = self.reference()
        want, want_hist = self._summary(tab, ref)
        got = dict(s, row_extra_ps=s["blame"]["row_extra_ps"])
        bad = compare.channel_counters(
            got, want, tab["channel"], prog_chan,
            compare.CHANNEL_COUNTERS + ("row_extra_ps",))
        for key in ("n_retired", "span_ps"):
            bad += compare.mismatches(s[key], want[key])
        for key in ("fixed_ps", "join_ps"):
            bad += compare.mismatches(s["blame"][key], want["blame"][key])
        lat = compare.mismatches(hist["counts"], want_hist["counts"])
        for key in ("n", "min_ps", "max_ps"):
            lat += compare.mismatches(hist[key], want_hist[key])
        lat += compare.mismatches(s["quantiles_ps"], want["quantiles_ps"])
        return {"telemetry_mismatch": bad, "latency_mismatch": lat}


def _fixed_sum(tab: dict) -> int:
    return int(np.where(tab["valid"], tab["fixed"], 0).sum())
