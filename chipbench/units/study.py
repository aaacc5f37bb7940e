"""A unit of work that is one rack study: lower the hosts' requests, resolve
the schedule monolithically, then attribute latency and count per channel.

    build_workload -> simulate_auto -> attribute_latency + channel_telemetry

Unit ``u`` draws its requests from the seed and ``u``; the window's units
all differ.  Checked: the schedule (arrive, start, depart, complete of
every item) and the telemetry (the latency partition of every request, the
per-channel counters, the observation window) against the reference.
"""

from __future__ import annotations

import numpy as np

from chipbench import compare, generator
from chipbench.fabric import Fabric
from chipbench.reference import des
from chipbench.reference import telemetry as reftel
from chipbench.reference.lowering import lower

SPANS = ("build_workload", "simulate_auto", "attribute_latency",
         "channel_telemetry")


class Unit:
    def __init__(self, cfg, traffic, seed, options, span):
        import jax

        self.jax, self.span = jax, span
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.options = options
        self.fabric = Fabric(cfg)
        C = self.fabric.C
        self.attribute = jax.jit(C.attribute_latency)
        self.telemetry = jax.jit(C.channel_telemetry)
        self.counters = {"units": 0, "requests": 0, "fixpoint_calls": 0,
                         "rounds": 0, "serve_items": 0,
                         "oracle_fallbacks": 0, "unconverged": 0,
                         "fallback_requests": 0, "lowered_requests": 0}

    def requests(self, unit: int) -> dict:
        return generator.chunk(self.traffic, len(self.fabric.hosts),
                               self.seed, unit)

    def compile_jobs(self) -> dict:
        """The cell's programs at its shapes, for set-up to compile."""
        from repro.core.engine import _simulate_fixpoint, hop_ser_ps

        jax, jnp = self.jax, self.jax.numpy
        wl = self.fabric.lower(self.requests(0))
        ser = jax.ShapeDtypeStruct(wl.hops.channel.shape, jnp.int64)
        budget = jnp.int64(1)        # the round budget is a traced operand
        impl = self.options.kernel_impl
        sched = jax.eval_shape(
            lambda *a: _simulate_fixpoint(*a, impl=impl), wl.hops,
            wl.channels, wl.issue_ps, ser, budget, None)
        return {
            "fixpoint": (_simulate_fixpoint, wl.hops, wl.channels,
                         wl.issue_ps, ser, budget, None, impl),
            "serialization": (hop_ser_ps, wl.hops, wl.channels),
            "attribution": (self.attribute, wl.hops, wl.channels, sched,
                            wl.issue_ps),
            "telemetry": (self.telemetry, wl.hops, wl.channels, sched),
        }

    def warm_up(self):
        """Every program and shape of a study: one study."""
        self.run_unit(0)

    def run_unit(self, unit: int):
        span, C = self.span, self.fabric.C
        ck = self.requests(unit)
        with span("build_workload"):
            wl = self.fabric.lower(ck)
        with span("simulate_auto"):
            sched, used_oracle = C.simulate_auto(wl.hops, wl.channels,
                                                 wl.issue_ps, self.options)
        with span("attribute_latency"):
            att = self.attribute(wl.hops, wl.channels, sched, wl.issue_ps)
        with span("channel_telemetry"):
            tel = self.telemetry(wl.hops, wl.channels, sched)
        self.jax.block_until_ready((att, tel))
        n, h = wl.hops.channel.shape
        rounds = int(sched.rounds)
        c = self.counters
        c["units"] += 1
        c["requests"] += n
        c["lowered_requests"] += n
        c["fixpoint_calls"] += 1
        c["rounds"] += rounds
        c["serve_items"] += rounds * n * h
        c["oracle_fallbacks"] += int(used_oracle)
        c["fallback_requests"] += n if used_oracle else 0
        c["unconverged"] += int(used_oracle or not bool(sched.converged))
        return {"unit": unit, "sched": sched, "att": att, "tel": tel,
                "channel": wl.hops.channel}

    def reference(self, unit: int, **how):
        tab = lower(self.cfg, generator.flat(self.requests(unit)))
        ref = des.simulate(tab["channel"], tab["ser"], tab["direction"],
                           tab["row"], tab["fixed"], tab["valid"],
                           tab["issue"], tab["turnaround"], tab["row_hit"],
                           tab["row_miss"], **how)
        return tab, ref

    def check(self, out) -> dict:
        """Mismatch counts of the unit's outputs against the reference."""
        got = {k: np.asarray(v) for k, v in out["sched"]._asdict().items()
               if k in ("arrive", "start", "depart", "complete")}
        att = {k: np.asarray(v) for k, v in out["att"]._asdict().items()}
        tel = {k: np.asarray(v) for k, v in out["tel"]._asdict().items()}
        return self.compare(got, att, tel, np.asarray(out["channel"]),
                            out["unit"])

    def control(self, unit: int, kind: str) -> dict:
        """The same counts with the reference itself in the program's
        place, computed as `des.CONTROLS` ``kind`` says."""
        tab, r = self.reference(unit, **des.CONTROLS[kind])
        tel = reftel.channel_counters(tab, r, tab["n_channels"])
        tel["window_ps"] = reftel.span(tab, r)
        return self.compare(r, reftel.attribution(tab, r), tel,
                            tab["channel"], unit)

    def compare(self, got, att, tel, prog_chan, unit) -> dict:
        tab, ref = self.reference(unit)
        sched_bad = sum(compare.mismatches(got[k], ref[k])
                        for k in ("arrive", "start", "depart", "complete"))
        want_att = reftel.attribution(tab, ref)
        tel_bad = sum(compare.mismatches(att[k], want_att[k])
                      for k in want_att)
        want = reftel.channel_counters(tab, ref, tab["n_channels"])
        tel_bad += compare.channel_counters(tel, want, tab["channel"],
                                            prog_chan)
        tel_bad += compare.mismatches(tel["window_ps"],
                                      reftel.span(tab, ref))
        return {"schedule_mismatch": sched_bad, "telemetry_mismatch": tel_bad}
