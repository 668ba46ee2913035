import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run driver (deliverable (e)).

For every (architecture x input-shape x mesh) cell:
  jit(step).lower(**ShapeDtypeStructs).compile() under the production mesh,
  print memory_analysis() (proves it fits) and cost_analysis() (roofline),
  parse the optimized HLO for collective ops, lower each scan-body Fragment
  separately (XLA counts while bodies once — DESIGN.md §7), and persist a
  JSON record in benchmarks/results/dryrun/.

Meshes: single-pod (16,16)=(data,model), multi-pod (2,16,16)=(pod,data,model).
Run:  PYTHONPATH=src python -m repro.launch.dryrun --arch all --mesh both
"""

import argparse
import json
import pathlib
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs import registry
from repro.launch.mesh import dp_axes, make_production_mesh
from repro.models.config import ModelConfig
from repro.roofline import analyze
from repro.sharding import specs as sh
from repro.train import optimizer as opt
from repro.train.trainstep import (accum_steps_for, make_train_step,
                                   opt_config_for)

RESULTS = pathlib.Path(__file__).resolve().parents[3] / "benchmarks" \
    / "results" / "dryrun"


def _sds_tree(tree):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                        tree)


def frag_arg_sharding(cfg: ModelConfig, mesh, arg, kind):
    """Shardings for a roofline-fragment argument, per Fragment.arg_kinds."""
    dp = dp_axes(mesh)
    if kind == "params":
        return sh.param_shardings(cfg, mesh, arg)
    if kind == "cache":
        def leaf(path, x):
            if path and isinstance(arg, dict):
                return sh.cache_leaf_sharding(cfg, mesh, path, x)
            return NamedSharding(
                mesh, sh._fit(mesh, x.shape,
                              (dp,) + (None,) * (len(x.shape) - 1)))
        return jax.tree_util.tree_map_with_path(leaf, arg)
    # explicit trailing-dims tail
    tail = kind if kind else ()

    def bare(x):
        if tail:
            return NamedSharding(mesh, sh._fit(mesh, x.shape, tail))
        return NamedSharding(mesh, P(*(None,) * len(x.shape)))
    return jax.tree.map(bare, arg)


def _collect(compiled, chips_per_pod=analyze.CHIPS_PER_POD):
    ca = compiled.cost_analysis() or {}
    txt = compiled.as_text()
    colls = analyze.parse_collectives(txt, chips_per_pod)
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes": float(ca.get("bytes accessed", 0.0)),
        "collectives": [c.__dict__ for c in colls],
        "n_collectives": len(colls),
    }


def _memory(compiled):
    ma = compiled.memory_analysis()
    out = {}
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "alias_size_in_bytes",
              "generated_code_size_in_bytes"):
        out[k] = int(getattr(ma, k, 0) or 0)
    out["total_bytes_per_device"] = (
        out["argument_size_in_bytes"] + out["output_size_in_bytes"]
        + out["temp_size_in_bytes"] - out["alias_size_in_bytes"])
    return out


def _grad_wrap(fn, stop_param_grads: bool = False):
    """Lower fn together with its backward pass (train-mode fragments).

    stop_param_grads=True stops gradients at the first (param) argument:
    used for the COLLECTIVE count only — inside the real layer scan, the
    per-layer dW stays a local partial sum (reduced once per step, which the
    full/microbatch HLO already counts), so the all-reduce a standalone vjp
    emits per call is an accounting artifact, not program traffic."""
    def wrapped(*args):
        if stop_param_grads:
            fn2 = lambda p, *rest: fn(jax.lax.stop_gradient(p), *rest)
        else:
            fn2 = fn
        out, vjp = jax.vjp(fn2, *args)
        cts = jax.tree.map(lambda o: jnp.ones(o.shape, o.dtype), out)
        return vjp(cts)
    return wrapped


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             verbose: bool = True) -> dict:
    cfg = registry.get_config(arch)
    shape = registry.SHAPES[shape_name]
    ok, reason = registry.cell_supported(cfg, shape)
    mesh_name = "multi" if multi_pod else "single"
    rec: dict = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                 "timestamp": time.time()}
    if not ok:
        rec.update(status="SKIP", reason=reason)
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = int(np.prod(list(mesh.shape.values())))
    model = registry.make_model(cfg)
    batch_specs = registry.input_specs(cfg, shape)
    pspecs = model.param_specs()

    t0 = time.time()
    dp_size = int(np.prod([mesh.shape[a] for a in dp_axes(mesh)]))
    accum = 1
    with mesh:
        from repro.models import moe as moe_mod
        if cfg.seq_shard_activations and shape.kind in ("train", "prefill") \
                and not cfg.enc_dec:
            model.act_spec = P(dp_axes(mesh), "model", None)
        if cfg.moe and cfg.moe.num_experts % (
                mesh.shape["data"] * mesh.shape["model"]) == 0:
            # expert-major einsums + batch-major scatter/gather -> token
            # all-to-alls (production EP) instead of replication fallbacks
            moe_mod.set_buf_spec(P(None, ("data", "model"), None, None),
                                 P(dp_axes(mesh), None, None))
        else:
            moe_mod.set_buf_spec(None)
        pshard = sh.param_shardings(cfg, mesh, pspecs)
        bshard = sh.batch_shardings(cfg, mesh, batch_specs)
        if shape.kind == "train":
            accum = accum_steps_for(cfg, shape.global_batch, shape.seq_len,
                                    dp_size, mesh.shape["model"])
            rec["accum_steps"] = accum
            gspecs = (sh.grad_shardings(cfg, mesh, pspecs)
                      if accum > 1 else None)
            mb_sh = (jax.tree.map(
                lambda ns: NamedSharding(mesh, P(None, *ns.spec)), bshard)
                if accum > 1 else None)
            ocfg = opt_config_for(cfg)
            ospecs = opt.opt_state_specs(ocfg, pspecs)
            oshard = sh.opt_shardings(cfg, mesh, ospecs)
            step = make_train_step(model, ocfg, accum, gspecs, mb_sh)
            # explicit out_shardings: without them GSPMD replicates the
            # updated params/opt state (638 GiB/device of outputs + 11 TB
            # of temps measured on deepseek-671b) and donation can't alias
            jfn = jax.jit(step, in_shardings=(pshard, oshard, bshard),
                          out_shardings=(pshard, oshard, None),
                          donate_argnums=(0, 1))
            lowered = jfn.lower(pspecs, ospecs, batch_specs)
        elif shape.kind == "prefill":
            fn = registry.step_fn(cfg, shape, model)
            jfn = jax.jit(fn, in_shardings=(pshard, bshard))
            lowered = jfn.lower(pspecs, batch_specs)
        else:  # decode
            fn = registry.step_fn(cfg, shape, model)
            # cache-out shardings must match cache-in for donation to alias
            jfn = jax.jit(fn, in_shardings=(pshard, bshard),
                          out_shardings=(None, bshard["cache"]),
                          donate_argnums=(1,))
            lowered = jfn.lower(pspecs, batch_specs)
        compiled = lowered.compile()
        rec["lower_compile_s"] = time.time() - t0
        mem = _memory(compiled)
        full = _collect(compiled)
        if verbose:
            print(f"[{arch} x {shape_name} x {mesh_name}] "
                  f"compile {rec['lower_compile_s']:.1f}s  "
                  f"mem/device {mem['total_bytes_per_device']/2**30:.2f} GiB "
                  f"flops/chip {full['flops']:.3e} "
                  f"colls {full['n_collectives']}")
            print("  memory_analysis:", mem)

        # ---- scan-body fragments --------------------------------------
        # trip accounting with gradient accumulation (see DESIGN.md §7):
        #   total = full + (accum-1) x microbatch + accum x Σ frag_extra x frag
        frag_parts = []
        if not cfg.enc_dec:
            mode = shape.kind if shape.kind != "prefill" else "prefill"
            b, s = _cell_bs(cfg, shape)
            b_frag = max(b // accum, 1) if shape.kind == "train" else b
            for frag in model.fragments(mode, b_frag, s):
                kinds = frag.arg_kinds or ("params",) + ((),) * (
                    len(frag.args) - 1)
                in_sh = tuple(
                    frag_arg_sharding(cfg, mesh, a, kinds[i])
                    for i, a in enumerate(frag.args))
                try:
                    if shape.kind == "train":
                        fc = jax.jit(_grad_wrap(frag.fn),
                                     in_shardings=in_sh).lower(
                            *frag.args).compile()
                        part = _collect(fc)
                        if kinds[0] == "params":
                            # collectives from the artifact-free lowering
                            fc2 = jax.jit(
                                _grad_wrap(frag.fn, stop_param_grads=True),
                                in_shardings=in_sh).lower(
                                *frag.args).compile()
                            part["collectives"] = _collect(fc2)["collectives"]
                            part["n_collectives"] = len(part["collectives"])
                    else:
                        fc = jax.jit(frag.fn, in_shardings=in_sh).lower(
                            *frag.args).compile()
                        part = _collect(fc)
                    part["mult"] = frag.extra_trips * accum
                    part["name"] = frag.name
                    frag_parts.append(part)
                except Exception as e:  # fragment failures are non-fatal
                    frag_parts.append({"name": frag.name, "error": str(e)[:500],
                                       "mult": frag.extra_trips * accum})
        if accum > 1:
            # the microbatch grad body itself (counted once in full HLO)
            mb_specs = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(
                    (x.shape[0] // accum,) + x.shape[1:], x.dtype),
                batch_specs)
            mb_shard = sh.batch_shardings(cfg, mesh, mb_specs)

            def mb_grad(params, mb):
                return jax.grad(lambda p, m: model.loss(p, m)[0])(params, mb)
            try:
                fc = jax.jit(mb_grad, in_shardings=(pshard, mb_shard)).lower(
                    pspecs, mb_specs).compile()
                part = _collect(fc)
                part["mult"] = accum - 1
                part["name"] = "microbatch_grad"
                frag_parts.append(part)
            except Exception as e:
                frag_parts.append({"name": "microbatch_grad",
                                   "error": str(e)[:500], "mult": accum - 1})
        rec.update(status="OK", chips=chips, memory=mem, full=full,
                   fragments=frag_parts)
    return rec


def _cell_bs(cfg, shape):
    b, s = shape.global_batch, shape.seq_len
    if shape.kind == "decode":
        return b, s
    if cfg.frontend == "vision_stub":
        return b, s  # embed-level seq is still s (patches + text)
    return b, s


def roofline_record(rec: dict) -> dict | None:
    if rec.get("status") != "OK":
        return None
    parts = [dict(rec["full"], mult=1)]
    for f in rec.get("fragments", []):
        if "error" not in f:
            parts.append(dict(f))
    parts = [
        dict(p, collectives=[analyze.CollectiveOp(**c) if isinstance(c, dict)
                             else c for c in p.get("collectives", [])])
        for p in parts]
    terms = analyze.terms_from_parts(parts, rec["chips"])
    return terms.as_dict()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi",
                                                       "both"])
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--out", default=str(RESULTS))
    args = ap.parse_args()

    archs = registry.ARCH_IDS if args.arch == "all" else [args.arch]
    shapes = list(registry.SHAPES) if args.shape == "all" else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    outdir = pathlib.Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)

    n_ok = n_skip = n_fail = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                name = f"{arch}__{shape}__{'multi' if mp else 'single'}.json"
                path = outdir / name
                if path.exists() and not args.force:
                    old = json.loads(path.read_text())
                    print(f"[cached] {name}: {old.get('status')}")
                    n_ok += old.get("status") == "OK"
                    n_skip += old.get("status") == "SKIP"
                    n_fail += old.get("status") == "FAIL"
                    continue
                try:
                    rec = run_cell(arch, shape, mp)
                except Exception as e:
                    traceback.print_exc()
                    rec = {"arch": arch, "shape": shape,
                           "mesh": "multi" if mp else "single",
                           "status": "FAIL", "error": str(e)[:2000]}
                rl = roofline_record(rec)
                if rl:
                    rec["roofline"] = rl
                    print(f"  roofline: compute {rl['t_compute']:.4f}s "
                          f"memory {rl['t_memory']:.4f}s "
                          f"collective {rl['t_collective']:.4f}s "
                          f"-> {rl['bottleneck']}-bound")
                path.write_text(json.dumps(rec, indent=1, default=float))
                n_ok += rec["status"] == "OK"
                n_skip += rec["status"] == "SKIP"
                n_fail += rec["status"] == "FAIL"
    print(f"\nDRY-RUN SUMMARY: {n_ok} OK, {n_skip} SKIP, {n_fail} FAIL")
    return 0 if n_fail == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
