import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"  # fake host devices, never the chip

"""Multi-pod dry-run: prove every (architecture x input-shape x mesh)
combination lowers, compiles and fits, and extract the roofline terms.

MUST be run as a module: ``PYTHONPATH=src python -m repro.launch.dryrun
--arch nemotron-4-15b --shape train_4k [--multi-pod] [--packed] ...``.
The XLA_FLAGS line above executes before any jax import (jax pins the
device count at first init).

Per combination this script:
  1. builds the production mesh (16x16 single-pod / 2x16x16 multi-pod),
  2. builds the train/prefill/decode step via repro.distributed.step,
  3. lowers + compiles against ShapeDtypeStructs (no allocation),
  4. records memory_analysis / cost_analysis / HLO collective bytes,
  5. derives the three roofline terms and writes a JSON artifact under
     results/dryrun/.
"""
import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs.base import INPUT_SHAPES
from repro.configs.registry import ARCH_IDS, get_config
from repro.core.mechanisms import make_mechanism
from repro.distributed.step import (
    MeshPlan,
    batch_structs,
    make_decode_step,
    make_prefill_step,
    make_train_step,
)
from repro.launch import hlo_analysis
from repro.launch.mesh import (
    V5E,
    client_axes_of,
    make_production_mesh,
)
from repro.models import meta as meta_lib
from repro.optim import make_optimizer
from repro.optim.schedules import constant

SKIP_LONG_CONTEXT_REASON = (
    "full-attention architecture: long_500k requires sub-quadratic attention "
    "(DESIGN.md §Arch-applicability)"
)


def supports(arch_cfg, shape) -> tuple[bool, str]:
    if shape.name == "long_500k" and not arch_cfg.subquadratic:
        return False, SKIP_LONG_CONTEXT_REASON
    return True, ""


def build_step(cfg, plan, shape, *, mechanism="rqm", packed=False,
               q_chunk=None, remat=True, seq_parallel=None,
               sp_compress=False, agg_dtype="int32", zero1=False,
               kv_quant=False, ssm_chunk=None):
    """Returns (jitted_fn, example_args as ShapeDtypeStructs)."""
    if q_chunk is not None:
        cfg = dataclasses.replace(cfg, q_chunk=q_chunk)
    if ssm_chunk is not None and cfg.ssm is not None:
        cfg = dataclasses.replace(
            cfg, ssm=dataclasses.replace(cfg.ssm, chunk=ssm_chunk))
    if shape.kind == "train":
        mech = make_mechanism(mechanism, c=0.01)
        opt = make_optimizer("sgd")
        fn, specs = make_train_step(
            cfg, plan, mech, opt, constant(0.5), shape, packed=packed,
            remat=remat, seq_parallel=seq_parallel, sp_compress=sp_compress,
            agg_dtype=agg_dtype, zero1=zero1,
        )
        params = meta_lib.shape_dtype_structs(specs["param_meta"])
        opt_state = meta_lib.shape_dtype_structs(specs["opt_meta"]) if specs["opt_meta"] else ()
        step = jax.ShapeDtypeStruct((), jnp.int32)
        batch = batch_structs(cfg, shape)
        key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype)
        return fn, (params, opt_state, step, batch, key)
    if shape.kind == "prefill":
        fn, specs = make_prefill_step(
            cfg, plan, shape,
            seq_parallel=bool(seq_parallel), sp_compress=sp_compress,
        )
        params = meta_lib.shape_dtype_structs(specs["param_meta"])
        Pfx = cfg.frontend.prefix_len if cfg.frontend else 0
        toks = jax.ShapeDtypeStruct((shape.global_batch, shape.seq_len - Pfx), jnp.int32)
        if cfg.frontend is not None:
            pe = jax.ShapeDtypeStruct((shape.global_batch, Pfx, cfg.d_model), jnp.bfloat16)
            return fn, (params, toks, pe)
        return fn, (params, toks)
    # decode
    fn, specs = make_decode_step(cfg, plan, shape, kv_quant=kv_quant)
    params = meta_lib.shape_dtype_structs(specs["param_meta"])
    caches = meta_lib.shape_dtype_structs(specs["cache_meta"])
    toks = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    return fn, (params, caches, toks, pos)


def run_one(arch: str, shape_name: str, *, multi_pod: bool, mechanism="rqm",
            packed=False, q_chunk=None, remat=True, seq_parallel=None,
            sp_compress=False, agg_dtype="int32", zero1=False,
            kv_quant=False, ssm_chunk=None,
            out_dir="results/dryrun", tag="") -> dict:
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    ok, reason = supports(cfg, shape)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    rec = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_name,
        "mechanism": mechanism if shape.kind == "train" else None,
        "packed": packed,
        "sp_compress": sp_compress,
        "agg_dtype": agg_dtype,
        "zero1": zero1,
        "kv_quant": kv_quant,
        "seq_parallel": seq_parallel,
        "tag": tag,
    }
    def _write(r):
        os.makedirs(out_dir, exist_ok=True)
        suffix = f"_{tag}" if tag else ""
        fname = f"{arch}_{shape_name}_{mesh_name}{suffix}.json"
        with open(os.path.join(out_dir, fname), "w") as f:
            json.dump(r, f, indent=2)

    if not ok:
        rec["status"] = "skipped"
        rec["reason"] = reason
        _write(rec)
        return rec
    mesh = make_production_mesh(multi_pod=multi_pod)
    plan = MeshPlan(mesh=mesh, client_axes=client_axes_of(mesh))
    n_dev = mesh.devices.size
    t0 = time.time()
    try:
        with jax.set_mesh(mesh):
            fn, args = build_step(
                cfg, plan, shape, mechanism=mechanism, packed=packed,
                q_chunk=q_chunk, remat=remat, seq_parallel=seq_parallel,
                sp_compress=sp_compress, agg_dtype=agg_dtype, zero1=zero1,
                kv_quant=kv_quant, ssm_chunk=ssm_chunk,
            )
            lowered = fn.lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
            ma = compiled.memory_analysis()
            ca = compiled.cost_analysis()
            hlo = compiled.as_text()
    except Exception as e:
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
        _write(rec)
        return rec

    coll = hlo_analysis.collective_bytes(hlo)
    flops = float(ca.get("flops", 0.0))
    bytes_accessed = float(ca.get("bytes accessed", 0.0))
    terms = hlo_analysis.roofline_terms(flops, bytes_accessed, coll.total_bytes, V5E)
    mflops_global = hlo_analysis.model_flops(cfg, shape, tp=plan.tp)
    mflops_per_dev = mflops_global / n_dev
    from repro.launch import memory_model

    mesh_shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    analytical = memory_model.estimate(
        cfg, shape, mesh_shape,
        seq_parallel=(seq_parallel if seq_parallel is not None else True),
        zero1=zero1, kv_quant=kv_quant,
    )
    mem = {
        # XLA-CPU stand-in numbers: the CPU thunk scheduler does not exploit
        # remat, so temp_bytes over-estimates the TPU peak (see §Dry-run
        # notes in EXPERIMENTS.md). Kept as an upper bound.
        "xla_cpu_argument_bytes": ma.argument_size_in_bytes,
        "xla_cpu_output_bytes": ma.output_size_in_bytes,
        "xla_cpu_temp_bytes": ma.temp_size_in_bytes,
        # analytical per-device HBM model — the fits check
        "analytical": {k: float(v) for k, v in analytical.items()},
        "hbm_limit": V5E["hbm_bytes"],
        "fits": bool(analytical["fits_16g"]),
    }
    rec.update(
        status="ok",
        devices=n_dev,
        lower_s=round(t_lower, 1),
        compile_s=round(t_compile, 1),
        per_device_flops=flops,
        per_device_hbm_bytes=bytes_accessed,
        collective=coll.summary(),
        roofline=terms,
        model_flops_per_device=mflops_per_dev,
        useful_flops_ratio=(mflops_per_dev / flops) if flops else None,
        memory=mem,
    )
    _write(rec)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="architecture id (default: all)")
    ap.add_argument("--shape", default=None, help="input shape (default: all)")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--mechanism", default="rqm",
                    help="mechanism spec: registered name or 'name:k=v,...' "
                         "string (e.g. 'qmgeo:c=0.05,m=16,r=0.6'); any "
                         "registered mechanism lowers through the mesh step")
    ap.add_argument("--packed", action="store_true", help="lane-packed aggregation")
    ap.add_argument("--q-chunk", type=int, default=None)
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--no-seq-parallel", action="store_true",
                    help="disable Megatron sequence parallelism (perf baseline)")
    ap.add_argument("--seq-parallel", action="store_true",
                    help="force SP on (enables SP for prefill, which is "
                         "plain-TP by default)")
    ap.add_argument("--sp-compress", action="store_true",
                    help="int8-compressed SP entry all-gathers (§Perf)")
    ap.add_argument("--agg-dtype", default="int32",
                    choices=["int32", "int16", "auto"],
                    help="SecAgg level width on the wire (§Perf)")
    ap.add_argument("--zero1", action="store_true",
                    help="ZeRO-1 master/optimizer sharding over clients (§Perf)")
    ap.add_argument("--kv-quant", action="store_true",
                    help="int8-quantized KV cache for decode shapes (§Perf)")
    ap.add_argument("--ssm-chunk", type=int, default=None,
                    help="override the SSD chunk length (§Perf)")
    ap.add_argument("--tag", default="", help="suffix for the artifact file")
    ap.add_argument("--out-dir", default="results/dryrun")
    args = ap.parse_args()

    archs = [args.arch] if args.arch else list(ARCH_IDS)
    shapes = [args.shape] if args.shape else list(INPUT_SHAPES)
    for arch in archs:
        for shape in shapes:
            rec = run_one(
                arch, shape, multi_pod=args.multi_pod, mechanism=args.mechanism,
                packed=args.packed, q_chunk=args.q_chunk,
                remat=not args.no_remat,
                seq_parallel=(False if args.no_seq_parallel
                              else (True if args.seq_parallel else None)),
                sp_compress=args.sp_compress, agg_dtype=args.agg_dtype,
                zero1=args.zero1, kv_quant=args.kv_quant,
                ssm_chunk=args.ssm_chunk,
                out_dir=args.out_dir, tag=args.tag,
            )
            status = rec["status"]
            extra = ""
            if status == "ok":
                r = rec["roofline"]
                extra = (f"compute={r['compute_s']*1e3:.2f}ms "
                         f"memory={r['memory_s']*1e3:.2f}ms "
                         f"coll={r['collective_s']*1e3:.2f}ms "
                         f"dom={r['dominant']} "
                         f"hbm={rec['memory']['analytical']['total']/2**30:.2f}GiB "
                         f"fits={rec['memory']['fits']} "
                         f"(lower {rec['lower_s']}s compile {rec['compile_s']}s)")
            elif status == "error":
                extra = rec["error"][:200]
            else:
                extra = rec["reason"][:80]
            print(f"[{status:7s}] {arch} x {shape} x {rec['mesh']} {extra}", flush=True)


if __name__ == "__main__":
    main()
