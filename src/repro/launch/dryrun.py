import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture × shape × mesh).

The two lines above MUST precede any other import (jax locks the device count
at first init).  Each cell:

    with mesh:
        lowered = jax.jit(step, in_shardings=…, out_shardings=…).lower(**specs)
        compiled = lowered.compile()
        print(compiled.memory_analysis())     # proves it fits
        print(compiled.cost_analysis())       # FLOPs/bytes for §Roofline

Results (memory, cost, collective stats, roofline terms) accumulate in a JSON
keyed by (arch, shape, mesh, variant) — benchmarks/roofline.py reads it.

Usage:
    python -m repro.launch.dryrun --arch yi-9b --shape train_4k
    python -m repro.launch.dryrun --arch yi-9b --shape train_4k --multi-pod
    python -m repro.launch.dryrun --all [--multi-pod] [--out results/dryrun.json]
    (perf variants: --remat full --gather-dtype bfloat16 --microbatches 4 ...)
"""

import argparse
import functools
import json
import subprocess
import sys
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.configs.shapes import SHAPES
from repro.launch import hlo_analysis as ha
from repro.launch import hlo_cost
from repro.launch.mesh import make_ctx, make_production_mesh
from repro.models import lm
from repro.parallel.mesh_ctx import mesh_context
from repro.parallel.sharding import (cache_shardings, input_shardings,
                                     param_shardings, safe_spec)
from repro.serve.engine import make_decode_step, make_prefill_step
from repro.train.step import make_train_step, train_state_shapes

DEFAULT_OUT = "results/dryrun.json"


def _serve_dtype(tree, dtype=jnp.bfloat16):
    """Serving weights are stored bf16 (standard practice)."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(
            s.shape, dtype if jnp.issubdtype(s.dtype, jnp.floating) else s.dtype),
        tree)


def _apply_overrides(cfg, ov: Dict[str, Any]):
    fields = {k: v for k, v in ov.items() if v is not None and k in
              ("remat", "gather_dtype", "scan_layers", "compute_dtype")}
    return cfg.replace(**fields) if fields else cfg


def variant_key(ov: Dict[str, Any]) -> str:
    parts = [f"{k}={v}" for k, v in sorted(ov.items())
             if v not in (None, False) and k != "out"]
    return ",".join(parts) or "baseline"


def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             overrides: Optional[Dict[str, Any]] = None,
             verbose: bool = True) -> Dict[str, Any]:
    overrides = overrides or {}
    shape = SHAPES[shape_name]
    skip = configs.skip_reason(arch, shape_name)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    rec: Dict[str, Any] = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "kind": shape.kind, "variant": variant_key(overrides), "skip": skip,
    }
    if skip:
        return rec

    cfg = _apply_overrides(configs.get(arch), overrides)
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.size
    ctx = make_ctx(mesh, fsdp_over_pod=bool(overrides.get("fsdp_over_pod")),
                   seq_shard_activations=bool(overrides.get("seq_shard")),
                   shard_kv_seq=bool(overrides.get("shard_kv_seq")))
    rec["devices"] = n_dev

    t0 = time.time()
    with mesh_context(ctx):
        if shape.kind == "train":
            state = train_state_shapes(cfg)
            state_sh = param_shardings(state, ctx)
            batch = configs.input_specs(cfg, shape)
            batch_sh = input_shardings(ctx, batch)
            step = make_train_step(cfg, microbatches=int(overrides.get("microbatches") or 1))
            jitted = jax.jit(step, in_shardings=(state_sh, batch_sh),
                             out_shardings=(state_sh, None), donate_argnums=0)
            lowered = jitted.lower(state, batch)
        elif shape.kind == "prefill":
            params = _serve_dtype(lm.init_shapes(cfg))
            p_sh = param_shardings(params, ctx)
            inputs = configs.input_specs(cfg, shape)
            in_sh = input_shardings(ctx, inputs)
            fn = make_prefill_step(cfg, max_len=shape.seq_len)
            cache_sds, logits_sds = jax.eval_shape(fn, params, inputs)
            c_sh = cache_shardings(cache_sds, ctx)
            l_sh = NamedSharding(ctx.mesh, safe_spec(
                logits_sds.shape, [tuple(ctx.batch_axes), ctx.model_axis], mesh))
            jitted = jax.jit(fn, in_shardings=(p_sh, in_sh),
                             out_shardings=(c_sh, l_sh))
            lowered = jitted.lower(params, inputs)
        else:                                       # decode
            params = _serve_dtype(lm.init_shapes(cfg))
            p_sh = param_shardings(params, ctx)
            inputs = configs.input_specs(cfg, shape)
            tok_sh = input_shardings(ctx, inputs["token"])
            cache_sds = _serve_dtype(inputs["cache"])
            c_sh = cache_shardings(cache_sds, ctx)
            fn = make_decode_step(cfg)
            logits_sds, _ = jax.eval_shape(fn, params, inputs["token"], cache_sds)
            l_sh = NamedSharding(ctx.mesh, safe_spec(
                logits_sds.shape, [tuple(ctx.batch_axes), ctx.model_axis], mesh))
            jitted = jax.jit(fn, in_shardings=(p_sh, tok_sh, c_sh),
                             out_shardings=(l_sh, c_sh), donate_argnums=2)
            lowered = jitted.lower(params, inputs["token"], cache_sds)
        rec["lower_s"] = round(time.time() - t0, 2)

        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 2)

        mem = compiled.memory_analysis()
        cost_raw = compiled.cost_analysis()
        if verbose:
            print(mem)
            print({k: v for k, v in cost_raw.items()
                   if k in ("flops", "bytes accessed", "transcendentals")})
        rec["memory"] = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
            "output_bytes": getattr(mem, "output_size_in_bytes", 0),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
            "code_bytes": getattr(mem, "generated_code_size_in_bytes", 0),
            "alias_bytes": getattr(mem, "alias_size_in_bytes", 0),
        }
        # peak working set ≈ args + outputs - aliased(donated) + temps
        m = rec["memory"]
        m["peak_bytes"] = (m["argument_bytes"] + m["output_bytes"]
                           + m["temp_bytes"] - m["alias_bytes"])
        # raw cost_analysis counts while bodies ONCE (scan-invariant) — kept
        # only as provenance; the roofline uses the trip-corrected walker.
        rec["cost_raw"] = {"flops": float(cost_raw.get("flops", 0.0)),
                           "bytes_accessed": float(cost_raw.get("bytes accessed", 0.0))}

        hlo = compiled.as_text()
        cost = hlo_cost.analyze(hlo, n_dev)
        rec["cost"] = cost.as_dict()
        mf = ha.model_flops(cfg, shape.kind, shape.seq_len, shape.global_batch)
        # memory term uses the TPU-fusion byte estimate (bytes_fused);
        # bytes_accessed (CPU-fusion granularity) is kept as the upper bound.
        rl = ha.roofline_terms(
            {"flops": cost.flops, "bytes accessed": cost.bytes_fused},
            wire_bytes=cost.wire_bytes, model_flops_per_device=mf / n_dev)
        rec["roofline"] = rl.as_dict()
        rec["ok"] = True
    return rec


# ==========================================================================
# Results store
# ==========================================================================


def record_key(rec: Dict[str, Any]) -> str:
    return f"{rec['arch']}|{rec['shape']}|{rec['mesh']}|{rec.get('variant','baseline')}"


def save_record(rec: Dict[str, Any], out_path: str) -> None:
    os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
    data = {}
    if os.path.exists(out_path):
        with open(out_path) as f:
            data = json.load(f)
    data[record_key(rec)] = rec
    tmp = out_path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(data, f, indent=1, sort_keys=True)
    os.replace(tmp, out_path)


# ==========================================================================
# CLI
# ==========================================================================


def _parser():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--arch", choices=list(configs.ARCHS))
    p.add_argument("--shape", choices=list(SHAPES))
    p.add_argument("--multi-pod", action="store_true")
    p.add_argument("--all", action="store_true",
                   help="sweep every (arch × shape) as subprocesses")
    p.add_argument("--out", default=DEFAULT_OUT)
    p.add_argument("--timeout", type=int, default=3000)
    # §Perf variant knobs
    p.add_argument("--remat", choices=["none", "dots", "full"])
    p.add_argument("--gather-dtype", dest="gather_dtype", choices=["bfloat16"])
    p.add_argument("--microbatches", type=int)
    p.add_argument("--fsdp-over-pod", dest="fsdp_over_pod", action="store_true")
    p.add_argument("--seq-shard", dest="seq_shard", action="store_true",
                   help="sequence-shard block-boundary activations over model")
    p.add_argument("--shard-kv-seq", dest="shard_kv_seq", action="store_true",
                   help="flash-decoding: shard KV rings over model on S")
    p.add_argument("--no-scan", dest="scan_layers", action="store_false",
                   default=None)
    return p


def _overrides(args) -> Dict[str, Any]:
    return {k: getattr(args, k) for k in
            ("remat", "gather_dtype", "microbatches", "fsdp_over_pod",
             "seq_shard", "shard_kv_seq", "scan_layers")}


def sweep(args) -> int:
    failures = 0
    for arch, shape in configs.all_cells():
        if configs.skip_reason(arch, shape):
            save_record({"arch": arch, "shape": shape,
                         "mesh": "2x16x16" if args.multi_pod else "16x16",
                         "kind": SHAPES[shape].kind, "variant": "baseline",
                         "skip": configs.skip_reason(arch, shape)}, args.out)
            print(f"[skip] {arch} × {shape}: {configs.skip_reason(arch, shape)}")
            continue
        cmd = [sys.executable, "-m", "repro.launch.dryrun",
               "--arch", arch, "--shape", shape, "--out", args.out]
        if args.multi_pod:
            cmd.append("--multi-pod")
        for flag, val in (("--remat", args.remat),
                          ("--gather-dtype", args.gather_dtype),
                          ("--microbatches", args.microbatches)):
            if val:
                cmd += [flag, str(val)]
        if args.fsdp_over_pod:
            cmd.append("--fsdp-over-pod")
        if args.seq_shard:
            cmd.append("--seq-shard")
        t0 = time.time()
        r = subprocess.run(cmd, capture_output=True, text=True,
                           timeout=args.timeout)
        ok = r.returncode == 0
        failures += (not ok)
        print(f"[{'ok' if ok else 'FAIL'}] {arch} × {shape} "
              f"({time.time()-t0:.0f}s)")
        if not ok:
            print(r.stdout[-2000:])
            print(r.stderr[-4000:])
    return failures


def main() -> int:
    args = _parser().parse_args()
    if args.all:
        return sweep(args)
    if not (args.arch and args.shape):
        _parser().error("--arch and --shape required (or --all)")
    try:
        rec = run_cell(args.arch, args.shape, multi_pod=args.multi_pod,
                       overrides=_overrides(args))
    except Exception:
        rec = {"arch": args.arch, "shape": args.shape,
               "mesh": "2x16x16" if args.multi_pod else "16x16",
               "kind": SHAPES[args.shape].kind,
               "variant": variant_key(_overrides(args)),
               "ok": False, "error": traceback.format_exc(limit=20)}
        save_record(rec, args.out)
        print(rec["error"])
        return 1
    save_record(rec, args.out)
    if rec.get("skip"):
        print(f"skipped: {rec['skip']}")
    elif rec.get("ok"):
        rl = rec["roofline"]
        print(f"{args.arch} × {args.shape} on {rec['mesh']} [{rec['variant']}]: "
              f"compute {rl['compute_s']*1e3:.2f}ms | memory {rl['memory_s']*1e3:.2f}ms | "
              f"collective {rl['collective_s']*1e3:.2f}ms → {rl['dominant']}-bound; "
              f"peak/device {rec['memory']['peak_bytes']/2**30:.2f} GiB; "
              f"roofline fraction {rl['roofline_fraction']:.3f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
