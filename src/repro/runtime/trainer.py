"""Trainer: sharded train step, microbatching, fault tolerance, metrics.

The step function is one pjit'd program: microbatch gradient accumulation via
lax.scan (overlappable with the FSDP gathers by XLA), AdamW with
ZeRO-sharded state, LR schedule, gradient clipping.  Around it: checkpoint
save/auto-resume (atomic, async), straggler detection hooks, and the ESF
fabric cost model for step-time sanity reports.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp

from repro.checkpoint import checkpoint as ckpt
from repro.models import transformer as TF
from repro.models import model_zoo as zoo
from repro.optim import adamw, schedules
from repro.parallel.sharding import (ShardingRules, logical, param_specs,
                                     tree_as_shardings, use_rules)
from repro.runtime.straggler import StragglerDetector


@dataclass
class TrainConfig:
    steps: int = 100
    microbatches: int = 1
    peak_lr: float = 3e-4
    warmup_steps: int = 20
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    ckpt_dir: str = ""
    ckpt_every: int = 200
    async_ckpt: bool = True
    log_every: int = 10
    schedule: str = "warmup_cosine"


class Trainer:
    def __init__(self, cfg, train_cfg: TrainConfig, mesh,
                 rules: ShardingRules | None = None):
        self.cfg = cfg
        self.tc = train_cfg
        self.mesh = mesh
        self.rules = rules or ShardingRules()
        self.detector = StragglerDetector()
        self.metrics_log: list[dict] = []
        self._build()

    # ------------------------------------------------------------------
    def _build(self):
        cfg, tc = self.cfg, self.tc
        sched_fn = getattr(schedules, tc.schedule)

        def train_step(params, opt_state, batch):
            mb = tc.microbatches

            def micro(carry, mb_batch):
                acc = carry
                (loss, metrics), grads = jax.value_and_grad(
                    lambda p: TF.loss_fn(p, cfg, mb_batch), has_aux=True
                )(params)
                acc = jax.tree.map(
                    lambda a, g: a + g.astype(jnp.float32) / mb, acc, grads)
                return acc, (loss, metrics["xent"])

            zeros = jax.tree.map(
                lambda x: jnp.zeros(x.shape, jnp.float32), params)
            split = jax.tree.map(
                lambda x: x.reshape(mb, x.shape[0] // mb, *x.shape[1:]), batch)
            grads, (losses, xents) = jax.lax.scan(micro, zeros, split)
            lr = sched_fn(opt_state.step, peak_lr=tc.peak_lr,
                          warmup_steps=tc.warmup_steps, total_steps=tc.steps)
            new_params, new_state, om = adamw.update(
                opt_state, grads, params, lr=lr,
                weight_decay=tc.weight_decay, grad_clip=tc.grad_clip)
            return new_params, new_state, {
                "loss": jnp.mean(losses), "xent": jnp.mean(xents),
                "lr": lr, **om}

        with jax.set_mesh(self.mesh), use_rules(self.rules):
            axes = TF.param_axes(cfg)
            pspecs = param_specs(axes)
            ospecs = adamw.state_axes(pspecs)
            bspec = logical("batch", None)
            self.param_shardings = psh = tree_as_shardings(self.mesh, pspecs)
            osh = tree_as_shardings(self.mesh, ospecs)
            bsh = tree_as_shardings(
                self.mesh, jax.tree.map(lambda _: bspec,
                                        {"tokens": 0, "labels": 0}))
            self.step_fn = jax.jit(
                train_step,
                in_shardings=(psh, osh, bsh),
                out_shardings=(psh, osh, None),
                donate_argnums=(0, 1),
            )

    # ------------------------------------------------------------------
    def init_state(self, seed: int = 0):
        cfg = self.cfg
        with jax.set_mesh(self.mesh), use_rules(self.rules):
            pspecs = param_specs(TF.param_axes(cfg))
            init = jax.jit(lambda k: TF.init_params(cfg, k),
                           out_shardings=tree_as_shardings(self.mesh, pspecs))
            params = init(jax.random.key(seed))
            opt = jax.jit(adamw.init,
                          out_shardings=tree_as_shardings(
                              self.mesh, adamw.state_axes(pspecs)))(params)
        return params, opt

    def maybe_resume(self, params, opt_state):
        if not self.tc.ckpt_dir:
            return params, opt_state, 0
        step = ckpt.latest_step(self.tc.ckpt_dir)
        if step is None:
            return params, opt_state, 0
        (params, opt_state), step = ckpt.restore(
            self.tc.ckpt_dir, (params, opt_state))
        return params, opt_state, step

    # ------------------------------------------------------------------
    def fit(self, source, params=None, opt_state=None, start_step: int = 0):
        if params is None:
            params, opt_state = self.init_state()
            params, opt_state, start_step = self.maybe_resume(params, opt_state)
        tc = self.tc
        with jax.set_mesh(self.mesh), use_rules(self.rules):
            for step in range(start_step, tc.steps):
                batch = source.batch(step)
                t0 = time.perf_counter()
                params, opt_state, metrics = self.step_fn(
                    params, opt_state, batch)
                metrics = {k: float(v) for k, v in metrics.items()}
                dt = time.perf_counter() - t0
                verdict = self.detector.observe(0, dt)
                metrics.update(step=step, step_time_s=dt, straggler=verdict)
                self.metrics_log.append(metrics)
                if step % tc.log_every == 0:
                    print(f"step {step:5d} loss {metrics['loss']:.4f} "
                          f"lr {metrics['lr']:.2e} {dt * 1e3:.0f} ms",
                          flush=True)
                if tc.ckpt_dir and step and step % tc.ckpt_every == 0:
                    ckpt.save(tc.ckpt_dir, step, (params, opt_state),
                              blocking=not tc.async_ckpt)
        if tc.ckpt_dir:
            ckpt.save(tc.ckpt_dir, tc.steps, (params, opt_state))
        return params, opt_state
