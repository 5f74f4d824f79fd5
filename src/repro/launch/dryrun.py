import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("REPRO_EXTRA_XLA_FLAGS", ""))
# ^ MUST precede every other import (jax locks device count on first init).

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this builds the production mesh, derives in/out shardings from
the logical rules, lowers the appropriate step function against
ShapeDtypeStruct inputs (NO device allocation), compiles, and records:

  * memory analysis (bytes per device — proves the cell fits),
  * cost analysis  (per-device HLO FLOPs / bytes — roofline numerators),
  * collective stats parsed from the optimized HLO (bytes + op counts),
  * the three roofline terms (seconds) + the dominant bottleneck,
  * MODEL_FLOPS (6ND train / 2ND inference) and the useful-compute ratio.

Usage:
  python -m repro.launch.dryrun --arch qwen3_4b --shape train_4k
  python -m repro.launch.dryrun --arch all --shape all --multi-pod both \
      --out results/dryrun
Options:
  --path shortcut|paged   decode access path (default shortcut; paged is the
                          traditional-directory baseline for §Perf)
  --opt  <key=val,...>    perf-iteration overrides (see OPTIMIZATIONS)
"""
import argparse
import dataclasses
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.compile_cache import enable_compile_cache
from repro.configs import base as cfg_base
from repro.configs.base import ArchConfig, get
from repro.data.pipeline import make_batch_specs
from repro.distributed import sharding as shd
from repro.launch import hlo_analysis as hlo
from repro.launch import hlo_cost
from repro.launch.mesh import make_production_mesh
from repro.launch.shapes import SHAPES, SKIP, cell_status, input_specs
from repro.optim.schedule import wsd_schedule
from repro.runtime import serve as serve_mod
from repro.runtime.train import make_train_step, opt_struct, param_struct


def _apply_overrides(cfg: ArchConfig, opt: dict) -> ArchConfig:
    """Perf-iteration config overrides (--opt key=val,...)."""
    fields = {f.name for f in dataclasses.fields(cfg)}
    repl = {}
    for k, v in opt.items():
        if k in fields:
            cur = getattr(cfg, k)
            repl[k] = type(cur)(v) if cur is not None else v
    return dataclasses.replace(cfg, **repl) if repl else cfg


def lower_cell(arch: str, shape: str, *, multi_pod: bool,
               path: str = "shortcut", opt: dict | None = None,
               dtype=jnp.bfloat16) -> dict:
    """Lower + compile one cell; returns the result record."""
    opt = opt or {}
    cfg = _apply_overrides(get(arch), opt)
    status = cell_status(cfg, shape)
    rec = {"arch": arch, "shape": shape,
           "mesh": "2x16x16" if multi_pod else "16x16",
           "path": path, "opt": opt, "status": status}
    if status == SKIP:
        rec["reason"] = "long_500k needs sub-quadratic decode; " \
            "full-attention arch (documented in DESIGN.md §5)"
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    spec = SHAPES[shape]
    t0 = time.time()

    grad_accum = int(opt.get("grad_accum", 1))
    remat = bool(int(opt.get("remat", 1)))
    factored = bool(int(opt.get("factored", cfg.num_params() > 3e10)))

    with shd.activate_mesh(mesh):
        if spec.kind == "train":
            p_struct = param_struct(cfg, dtype)
            o_struct = opt_struct(p_struct, factored=factored)
            batch = input_specs(cfg, shape)["batch"]
            p_specs = shd.param_specs(p_struct, mesh)
            # optimizer states mirror param sharding; scalars replicated
            o_specs = _opt_specs(o_struct, p_struct, mesh)
            b_specs = shd.batch_spec(batch, mesh)
            step = make_train_step(
                cfg, lr_fn=lambda s: wsd_schedule(
                    s, peak_lr=3e-4, warmup_steps=100, total_steps=10000),
                grad_accum=grad_accum, remat=remat, factored=factored).fn
            jitted = jax.jit(
                step,
                in_shardings=(p_specs, o_specs, b_specs),
                out_shardings=(p_specs, o_specs, None),
                donate_argnums=(0, 1))
            lowered = jitted.lower(p_struct, o_struct, batch)
            arg_structs = (p_struct, o_struct, batch)
            arg_specs = (p_specs, o_specs, b_specs)
            tokens = spec.global_batch * spec.seq_len
            model_flops = 6.0 * cfg.num_active_params() * tokens

        elif spec.kind == "prefill":
            p_struct = param_struct(cfg, dtype)
            batch = input_specs(cfg, shape)["batch"]
            p_specs = shd.param_specs(p_struct, mesh)
            b_specs = shd.batch_spec(batch, mesh)
            prefill = serve_mod.make_prefill_step(cfg, s_cap=spec.seq_len,
                                                  dtype=dtype)
            jitted = jax.jit(prefill, in_shardings=(p_specs, b_specs),
                             out_shardings=None)
            lowered = jitted.lower(p_struct, batch)
            arg_structs = (p_struct, batch)
            arg_specs = (p_specs, b_specs)
            tokens = spec.global_batch * spec.seq_len
            model_flops = 2.0 * cfg.num_active_params() * tokens

        else:  # decode
            p_struct = param_struct(cfg, dtype)
            p_specs = shd.param_specs(p_struct, mesh)
            ins = input_specs(cfg, shape, dtype=dtype)
            if path == "paged":
                from repro.kvcache import paged_cache as pc
                bs = int(opt.get("block_size", 16))
                B, S = spec.global_batch, spec.seq_len
                nblocks = B * (S // bs + 1)
                cache = jax.eval_shape(lambda: pc.cache_create(
                    cfg.num_layers, nblocks, bs, cfg.num_kv_heads,
                    cfg.resolved_head_dim, B, S // bs + 1, dtype))
                c_names = pc.PagedKVCache(
                    k_pool=["layer", "blocks", None, "kv_heads", "head_dim"],
                    v_pool=["layer", "blocks", None, "kv_heads", "head_dim"],
                    block_tables=["kv_seqs", None], seq_lens=["kv_seqs"],
                    free_ring=[None], free_head=[], free_count=[])
                c_specs = pc.PagedKVCache(*[
                    NamedSharding(mesh, shd.logical_spec(s.shape, n, mesh))
                    for s, n in zip(cache, c_names)])
                token = ins["token"]
                seq_ids = jax.ShapeDtypeStruct((spec.global_batch,), jnp.int32)
                tok_spec = NamedSharding(mesh, shd.logical_spec(
                    token.shape, ["batch"], mesh))
                step = serve_mod.make_paged_serve_step(cfg)
                jitted = jax.jit(
                    step, in_shardings=(p_specs, c_specs, tok_spec, tok_spec),
                    out_shardings=(tok_spec, c_specs), donate_argnums=(1,))
                lowered = jitted.lower(p_struct, cache, token, seq_ids)
                arg_structs = (p_struct, cache, token, seq_ids)
                arg_specs = (p_specs, c_specs, tok_spec, tok_spec)
            else:
                state = ins["state"]
                s_specs = serve_mod.decode_state_specs(cfg, state, mesh)
                token = ins["token"]
                tok_spec = NamedSharding(mesh, shd.logical_spec(
                    token.shape, ["batch"], mesh))
                step = serve_mod.make_serve_step(cfg)
                jitted = jax.jit(
                    step, in_shardings=(p_specs, s_specs, tok_spec),
                    out_shardings=(tok_spec, s_specs), donate_argnums=(1,))
                lowered = jitted.lower(p_struct, state, token)
                arg_structs = (p_struct, state, token)
                arg_specs = (p_specs, s_specs, tok_spec)
            tokens = spec.global_batch  # one token per sequence per step
            model_flops = 2.0 * cfg.num_active_params() * tokens

    t_lower = time.time() - t0
    compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower

    # trip-count-aware analysis over the optimized per-device HLO (XLA's
    # own cost_analysis counts while bodies once; see hlo_cost.py)
    totals = hlo_cost.analyze(compiled.as_text())
    cost = {"hlo_flops": totals.flops, "hlo_bytes": totals.bytes}
    xla_cost = hlo.cost_numbers(compiled)
    mem = hlo.memory_numbers(compiled)
    if mem["total_bytes"] == 0:
        mem["total_bytes"] = _sharded_arg_bytes(arg_structs, arg_specs)
        mem["argument_bytes"] = mem["total_bytes"]
        mem["source"] = "sharded-arg-fallback"
    terms = hlo.roofline_terms(cost["hlo_flops"], cost["hlo_bytes"],
                               totals.collective_bytes)
    per_device_model_flops = model_flops / chips

    rec.update({
        "chips": chips,
        "tokens_per_step": tokens,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "model_flops_global": model_flops,
        "model_flops_per_device": per_device_model_flops,
        **cost,
        "xla_cost_analysis": xla_cost,
        "useful_flops_ratio": (per_device_model_flops
                               / max(cost["hlo_flops"], 1.0)),
        "memory": mem,
        "collective_bytes": totals.collective_bytes,
        "collectives": {"bytes": totals.bytes_by_collective,
                        "count": totals.count_by_collective},
        "while_trips": totals.while_trips[:32],
        **terms,
    })
    # roofline fraction:
    #  - train/prefill (compute-dominated workloads): useful model compute
    #    time / the dominant-term time (an MFU-style number);
    #  - decode (memory-bound by nature): ideal bytes that MUST move per
    #    step (local params + live cache read once) / counted HLO bytes.
    bound = rec["step_s_lower_bound"]
    if spec.kind == "decode":
        ideal = _ideal_decode_bytes(arg_structs, arg_specs)
        rec["ideal_bytes_per_device"] = ideal
        rec["roofline_fraction"] = (
            (ideal / hlo.HBM_BW) / bound if bound > 0 else 0.0)
    else:
        rec["roofline_fraction"] = (
            per_device_model_flops / hlo.PEAK_FLOPS / bound
            if bound > 0 else 0.0)
    return rec


def _ideal_decode_bytes(arg_structs, arg_specs) -> int:
    """Local bytes a decode step cannot avoid touching once: parameters +
    KV/state cache (first two lowering args)."""
    return _sharded_arg_bytes(arg_structs[:2], arg_specs[:2])


def _opt_specs(o_struct, p_struct, mesh):
    """Optimizer state shardings mirror their parameter's sharding."""
    p_specs = shd.param_specs(p_struct, mesh)
    rep = NamedSharding(mesh, P())

    def v_spec(pspec, vleaf_tree):
        # factored dict {vr, vc}: derive from the param spec by dropping
        # the last / second-to-last dim's entry
        def reduce_spec(spec: NamedSharding, drop_axis: int, ndim: int):
            entries = list(spec.spec) + [None] * (ndim + 1 - len(spec.spec))
            del entries[drop_axis]
            while entries and entries[-1] is None:
                entries.pop()
            return NamedSharding(mesh, P(*entries))
        if isinstance(vleaf_tree, dict):
            nd = len(vleaf_tree["vr"].shape) + 1
            return {"vr": reduce_spec(pspec, nd - 1, nd - 1),
                    "vc": reduce_spec(pspec, nd - 2, nd - 1)}
        return pspec

    from repro.optim.adamw import AdamWState
    m_specs = p_specs
    v_specs = jax.tree.map(
        v_spec, p_specs, o_struct.v,
        is_leaf=lambda x: isinstance(x, NamedSharding))
    return AdamWState(step=rep, m=m_specs, v=v_specs)


def _sharded_arg_bytes(structs, specs) -> int:
    """Fallback per-device byte estimate: sum of local shard sizes."""
    total = 0
    flat_s = jax.tree.leaves(structs)
    flat_p = jax.tree.leaves(
        specs, is_leaf=lambda x: isinstance(x, NamedSharding))
    for s, p in zip(flat_s, flat_p):
        if not hasattr(s, "shape"):
            continue
        n = s.dtype.itemsize
        for d in s.shape:
            n *= d
        if isinstance(p, NamedSharding):
            try:
                shard_shape = p.shard_shape(s.shape)
                n = s.dtype.itemsize
                for d in shard_shape:
                    n *= d
            except Exception:
                pass
        total += n
    return total


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--multi-pod", choices=["no", "yes", "both"],
                    default="no")
    ap.add_argument("--path", choices=["shortcut", "paged"],
                    default="shortcut")
    ap.add_argument("--opt", default="",
                    help="comma-separated key=val config overrides")
    ap.add_argument("--out", default="",
                    help="directory for one JSON per cell")
    args = ap.parse_args(argv)
    enable_compile_cache()

    archs = list(cfg_base.ASSIGNED) if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    pods = {"no": [False], "yes": [True], "both": [False, True]}[
        args.multi_pod]
    opt = dict(kv.split("=", 1) for kv in args.opt.split(",") if kv)

    failures = 0
    for arch in archs:
        for shape in shapes:
            for mp in pods:
                tag = f"{arch}/{shape}/{'2x16x16' if mp else '16x16'}" \
                    f"/{args.path}"
                try:
                    rec = lower_cell(arch, shape, multi_pod=mp,
                                     path=args.path, opt=opt)
                except Exception as e:
                    failures += 1
                    rec = {"arch": arch, "shape": shape,
                           "mesh": "2x16x16" if mp else "16x16",
                           "path": args.path, "status": "error",
                           "error": f"{type(e).__name__}: {e}",
                           "trace": traceback.format_exc()[-2000:]}
                    print(f"[FAIL] {tag}: {rec['error']}", flush=True)
                else:
                    if rec["status"] == SKIP:
                        print(f"[SKIP] {tag}: {rec['reason']}", flush=True)
                    else:
                        print(
                            f"[ OK ] {tag}: mem/dev="
                            f"{rec['memory']['total_bytes']/2**30:.2f}GiB "
                            f"compute={rec['compute_s']*1e3:.2f}ms "
                            f"memory={rec['memory_s']*1e3:.2f}ms "
                            f"collective={rec['collective_s']*1e3:.2f}ms "
                            f"dom={rec['dominant']} "
                            f"roofline={rec['roofline_fraction']:.3f} "
                            f"(compile {rec['compile_s']:.0f}s)",
                            flush=True)
                if args.out:
                    os.makedirs(args.out, exist_ok=True)
                    opt_tag = "_" + "_".join(
                        f"{k}-{v}" for k, v in opt.items()) if opt else ""
                    fname = (f"{arch}__{shape}__"
                             f"{'2x16x16' if mp else '16x16'}__"
                             f"{args.path}{opt_tag}.json")
                    with open(os.path.join(args.out, fname), "w") as f:
                        json.dump(rec, f, indent=1, default=str)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
