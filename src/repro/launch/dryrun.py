import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell the appropriate step function (train / prefill / decode) is
pjit'd with the production sharding rules, lowered against ShapeDtypeStruct
inputs (no allocation), and compiled for the 16x16 single-pod and 2x16x16
multi-pod meshes.  Recorded per cell into the artifacts JSON:

  * memory_analysis()   — per-device argument/temp/output/alias bytes
                          (proves the cell fits 16 GB v5e HBM);
  * cost_analysis()     — per-device HLO FLOPs/bytes.  A `lax.scan` body is
                          counted ONCE (verified empirically), so a second
                          "period" program (one pattern period, same
                          shardings) is compiled and the roofline applies
                          total = full + (n_periods - 1) * period;
  * the collective schedule — op counts + per-device result bytes parsed
                          from compiled.as_text(), same trip-count correction.

Usage:
  python -m repro.launch.dryrun --arch llama3-8b --shape train_4k --multi-pod
  python -m repro.launch.dryrun --all --out artifacts/dryrun.json
"""

import argparse   # noqa: E402
import json       # noqa: E402
import re         # noqa: E402
import time       # noqa: E402
import traceback  # noqa: E402

import jax        # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.configs import (ARCH_IDS, SHAPES, get_config,  # noqa: E402
                           shape_applicable)
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.models import model_zoo as zoo  # noqa: E402
from repro.models import transformer as TF  # noqa: E402
from repro.models.layers import DTYPE  # noqa: E402
from repro.optim import adamw  # noqa: E402
from repro.parallel.sharding import (ShardingRules, logical,  # noqa: E402
                                     param_specs, prune_tree_specs,
                                     tree_as_shardings, use_rules)

COLLECTIVE_RE = re.compile(
    r"(\w[\w.]*)\s*=\s*([a-z0-9]+)\[([\d,]*)\][^=]*?"
    r"\b(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")

DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
               "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
               "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}


def parse_collectives(hlo_text: str) -> dict:
    """op kind -> [count, total per-device result bytes]."""
    out: dict[str, list] = {}
    for m in COLLECTIVE_RE.finditer(hlo_text):
        _, dt, dims, kind = m.groups()
        nbytes = DTYPE_BYTES.get(dt, 4)
        for d in dims.split(","):
            if d:
                nbytes *= int(d)
        ent = out.setdefault(kind, [0, 0])
        ent[0] += 1
        ent[1] += nbytes
    return out


def batch_specs_for(cfg, shape, kind):
    b = logical("batch", None)
    if kind == "train":
        specs = {"tokens": b, "labels": b}
        if cfg.vision_patches or cfg.enc_layers:
            specs["frontend_embeds"] = logical("batch", None, "embed_act")
        return {"batch": specs}
    if kind == "prefill":
        out = {"tokens": b}
        if cfg.vision_patches or cfg.enc_layers:
            out["frontend_embeds"] = logical("batch", None, "embed_act")
        return out
    return {"tokens": b, "positions": b}


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               rules: ShardingRules | None = None,
               with_period: bool = True, cfg_override=None) -> dict:
    cfg = cfg_override or get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    rec = {"arch": arch, "shape": shape_name,
           "mesh": "2x16x16" if multi_pod else "16x16"}
    if not ok:
        rec.update(status="skipped", reason=why)
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = rules or ShardingRules()
    kind = shape.kind

    t0 = time.time()
    with jax.set_mesh(mesh), use_rules(rules):
        aparams = zoo.abstract_params(cfg)
        pspecs = prune_tree_specs(param_specs(TF.param_axes(cfg)), aparams,
                                  mesh)
        inputs = zoo.input_specs(cfg, shape)
        bspecs = batch_specs_for(cfg, shape, kind)
        # prune batch shardings against the actual input shapes (e.g. the
        # long_500k global batch of 1 cannot shard over (pod, data))
        from repro.parallel.sharding import prune_spec_for_shape

        def _prune_inputs(specs, ins):
            return {k: prune_spec_for_shape(v, ins[k].shape, mesh)
                    if k in ins and hasattr(ins[k], "shape") else v
                    for k, v in specs.items()}

        if kind == "train":
            bspecs["batch"] = _prune_inputs(bspecs["batch"], inputs["batch"])
        else:
            bspecs = _prune_inputs(bspecs, inputs)

        if kind == "train":
            aopt = jax.eval_shape(adamw.init, aparams)
            ospecs = prune_tree_specs(adamw.state_axes(pspecs), aopt, mesh)

            def step(params, opt_state, batch):
                (loss, metrics), grads = jax.value_and_grad(
                    lambda p: TF.loss_fn(p, cfg, batch), has_aux=True)(params)
                new_p, new_s, om = adamw.update(opt_state, grads, params,
                                                lr=jnp.float32(1e-4))
                return new_p, new_s, loss

            jitted = jax.jit(
                step,
                in_shardings=tree_as_shardings(
                    mesh, (pspecs, ospecs, bspecs["batch"])),
                out_shardings=tree_as_shardings(mesh, (pspecs, ospecs, None)),
                donate_argnums=(0, 1))
            args = (aparams, aopt, inputs["batch"])
        elif kind == "prefill":
            acache = zoo.abstract_cache(cfg, shape.global_batch,
                                        shape.seq_len)
            cspecs = prune_tree_specs(
                param_specs(TF.cache_axes(cfg)), acache, mesh)

            def step(params, tokens, frontend_embeds=None):
                return TF.prefill(params, cfg, tokens, max_len=shape.seq_len,
                                  frontend_embeds=frontend_embeds)

            in_sh = [pspecs, bspecs["tokens"]]
            args = [aparams, inputs["tokens"]]
            if "frontend_embeds" in inputs:
                in_sh.append(bspecs["frontend_embeds"])
                args.append(inputs["frontend_embeds"])
            jitted = jax.jit(
                step, in_shardings=tree_as_shardings(mesh, tuple(in_sh)),
                out_shardings=tree_as_shardings(mesh, (None, cspecs)))
            args = tuple(args)
        else:  # decode / long_decode
            acache = inputs["cache"]
            cspecs = prune_tree_specs(
                param_specs(TF.cache_axes(cfg)), acache, mesh)

            def step(params, cache, tokens, positions):
                return TF.decode_step(params, cfg, cache, tokens, positions)

            jitted = jax.jit(
                step,
                in_shardings=tree_as_shardings(
                    mesh, (pspecs, cspecs, bspecs["tokens"],
                           bspecs["positions"])),
                out_shardings=tree_as_shardings(mesh, (None, cspecs)),
                donate_argnums=(1,))
            args = (aparams, acache, inputs["tokens"], inputs["positions"])

        lowered = jitted.lower(*args)
        rec["lower_s"] = round(time.time() - t0, 2)
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 2)

        ma = compiled.memory_analysis()
        rec["memory"] = {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
            "peak_per_device_gib": round(
                (ma.argument_size_in_bytes + ma.output_size_in_bytes
                 + ma.temp_size_in_bytes - ma.alias_size_in_bytes) / 2**30, 3),
        }
        ca = compiled.cost_analysis()
        rec["flops_once"] = float(ca.get("flops", 0.0))
        rec["bytes_once"] = float(ca.get("bytes accessed", 0.0))
        rec["collectives_once"] = parse_collectives(compiled.as_text())
        rec["n_periods"] = cfg.n_periods
        rec["status"] = "ok"

        # ---- per-period program for scan trip-count correction ----------
        if with_period and cfg.n_periods > 1:
            rec["period"] = _lower_period(cfg, shape, mesh, rules, pspecs,
                                          aparams, kind)
    return rec


def _lower_period(cfg, shape, mesh, rules, pspecs, aparams, kind) -> dict:
    """Compile ONE pattern period with identical shardings; its costs scale
    the scan body (n_periods - 1) more times in the roofline."""
    from repro.models.transformer import _period_fn

    b = shape.global_batch
    s = 1 if kind in ("decode", "long_decode") else shape.seq_len
    x_spec = jax.ShapeDtypeStruct((b, s, cfg.d_model), DTYPE)
    pos_spec = jax.ShapeDtypeStruct((b, s), jnp.int32)
    stage0 = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype),
        aparams["stages"])
    sspecs = jax.tree.map(lambda sp: P(*sp[1:]), pspecs["stages"],
                          is_leaf=lambda x: isinstance(x, P))

    enc_kv_spec = None
    if cfg.enc_layers:
        enc_kv_spec = {
            "k": jax.ShapeDtypeStruct((b, cfg.enc_frames, cfg.n_kv,
                                       cfg.head_dim), DTYPE),
            "v": jax.ShapeDtypeStruct((b, cfg.enc_frames, cfg.n_kv,
                                       cfg.head_dim), DTYPE),
        }

    if kind == "train":
        def period(sp, x, pos, ekv=None):
            def f(sp_, x_):
                y, _, aux = _period_fn(sp_, x_, pos, cfg, mode="train",
                                       enc_kv=ekv)
                return jnp.sum(y.astype(jnp.float32)) + aux
            g = jax.grad(f, argnums=(0, 1))(sp, x)
            return g
    else:
        def period(sp, x, pos, ekv=None):
            y, _, _ = _period_fn(sp, x, pos, cfg, mode="train",
                                 cache_len=shape.seq_len, enc_kv=ekv)
            return y

    from repro.parallel.sharding import prune_spec_for_shape
    x_sh = prune_spec_for_shape(logical("batch", None, "embed_act"),
                                x_spec.shape, mesh)
    pos_sh = prune_spec_for_shape(logical("batch", None), pos_spec.shape, mesh)
    in_sh = [sspecs, x_sh, pos_sh]
    args = [stage0, x_spec, pos_spec]
    if enc_kv_spec is not None:
        in_sh.append(jax.tree.map(
            lambda a: prune_spec_for_shape(
                logical("batch", None, "kv_heads", None), a.shape, mesh),
            enc_kv_spec))
        args.append(enc_kv_spec)
    jitted = jax.jit(period,
                     in_shardings=tree_as_shardings(mesh, tuple(in_sh)))
    lowered = jitted.lower(*args)
    compiled = lowered.compile()
    ca = compiled.cost_analysis()
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes": float(ca.get("bytes accessed", 0.0)),
        "collectives": parse_collectives(compiled.as_text()),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=tuple(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="artifacts/dryrun.json")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--no-period", action="store_true")
    args = ap.parse_args()

    cells = []
    if args.all:
        for arch in ARCH_IDS:
            for shape in SHAPES:
                for mp in (False, True):
                    cells.append((arch, shape, mp))
    else:
        cells.append((args.arch, args.shape, args.multi_pod))

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    results = {}
    if os.path.exists(args.out) and not args.force:
        results = json.load(open(args.out))

    for arch, shape, mp in cells:
        key = f"{arch}|{shape}|{'multi' if mp else 'single'}"
        if key in results and results[key].get("status") in ("ok", "skipped") \
                and not args.force:
            print(f"[skip cached] {key}")
            continue
        print(f"[dryrun] {key}", flush=True)
        try:
            rec = lower_cell(arch, shape, mp, with_period=not args.no_period)
        except Exception as e:  # noqa: BLE001
            rec = {"arch": arch, "shape": shape,
                   "mesh": "2x16x16" if mp else "16x16",
                   "status": "error", "error": f"{type(e).__name__}: {e}",
                   "trace": traceback.format_exc()[-2000:]}
        results[key] = rec
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
        status = rec.get("status")
        mem = rec.get("memory", {}).get("peak_per_device_gib", "-")
        print(f"  -> {status} (peak/device {mem} GiB, "
              f"lower {rec.get('lower_s', '-')}s, "
              f"compile {rec.get('compile_s', '-')}s)", flush=True)

    n_ok = sum(1 for r in results.values() if r.get("status") == "ok")
    n_skip = sum(1 for r in results.values() if r.get("status") == "skipped")
    n_err = sum(1 for r in results.values() if r.get("status") == "error")
    print(f"dryrun summary: ok={n_ok} skipped={n_skip} error={n_err}")
    if n_err:
        for k, r in results.items():
            if r.get("status") == "error":
                print(f"  ERROR {k}: {r['error']}")


if __name__ == "__main__":
    main()
