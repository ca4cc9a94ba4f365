import os
os.environ["XLA_FLAGS"] = os.environ.get(
    "REPRO_DRYRUN_XLA_FLAGS", "--xla_force_host_platform_device_count=512"
)
# The two lines above MUST run before any other import (jax locks the device
# count at first init). Everything below is ordinary.
_DOC = """Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this prints/records:
  - compiled.memory_analysis()  (per-device bytes: proves it fits)
  - compiled.cost_analysis()    (FLOPs / bytes for the roofline)
  - collective-op byte totals parsed from the optimized HLO
and appends the result to a JSON ledger. Usage:

  PYTHONPATH=src python -m repro.launch.dryrun --arch granite-8b \
      --shape train_4k --mesh single --out results/dryrun.json
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
"""

import argparse
import json
import re
import sys
import time
import traceback
from pathlib import Path

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.launch import sharding as SH
from repro.launch import specs as SP
from repro.launch.mesh import data_axes, make_production_mesh
from repro.models import decode as D
from repro.models import transformer as T
from repro.training import train_step as TS

_COLLECTIVES = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)
_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
}
_SHAPE_RE = re.compile(r"(pred|bf16|f16|f32|f64|s8|u8|s16|u16|s32|u32|s64|u64)\[([\d,]*)\]")


def _shape_bytes(text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def collective_bytes(hlo_text: str) -> dict:
    """Per-device link-traffic estimate from the optimized HLO.

    For each collective op we take its OUTPUT shape bytes and apply the ring
    traffic factor for its replica-group size g:
        all-gather          out*(g-1)/g      (out = gathered tensor)
        reduce-scatter      out*(g-1)        (out = scattered shard)
        all-reduce          2*out*(g-1)/g    (RS + AG)
        all-to-all          out*(g-1)/g
        collective-permute  out
    Loop bodies are counted once by HLO text just like cost_analysis — the
    roofline probes extrapolate (see benchmarks/roofline.py)."""
    out = {k: 0.0 for k in _COLLECTIVES}
    raw = {k: 0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        ls = line.strip()
        for kind in _COLLECTIVES:
            if f" {kind}(" in ls or f" {kind}-start(" in ls:
                lhs = ls.split("=", 1)
                shape_part = lhs[1] if len(lhs) > 1 else ls
                shape_part = shape_part.split(kind)[0]
                nbytes = _shape_bytes(shape_part)
                m = _GROUPS_RE.search(ls)
                g = int(m.group(2)) if m else 16
                g = max(g, 2)
                factor = {
                    "all-gather": (g - 1) / g,
                    "reduce-scatter": (g - 1),
                    "all-reduce": 2 * (g - 1) / g,
                    "all-to-all": (g - 1) / g,
                    "collective-permute": 1.0,
                }[kind]
                out[kind] += nbytes * factor
                raw[kind] += nbytes
                counts[kind] += 1
                break
    return {
        "bytes_by_kind": {k: round(v) for k, v in out.items()},
        "raw_out_bytes": raw,
        "counts": counts,
        "total_bytes": round(sum(out.values())),
    }


def run_cell(arch: str, shape_name: str, multi_pod: bool, fsdp=None,
             cfg_override=None, n_micro_override=None, quiet=False) -> dict:
    cfg = cfg_override if cfg_override is not None else configs.get(arch)
    shape = SP.SHAPES[shape_name]
    ok, why = SP.cell_supported(cfg, shape_name)
    if not ok:
        return {"arch": arch, "shape": shape_name,
                "mesh": "multi" if multi_pod else "single",
                "status": "skipped", "reason": why}

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_data = 1
    for a in data_axes(mesh):
        n_data *= mesh.shape[a]
    fsdp = SH.wants_fsdp(cfg) if fsdp is None else fsdp

    t0 = time.time()
    params_shape = T.abstract_params(cfg)
    serve = shape.kind == "decode"
    pspecs = SH.param_specs(cfg, params_shape, mesh, fsdp, serve=serve)
    pshard = SH.shardings_of(pspecs, mesh)

    if shape.kind == "train":
        state_shape = TS.abstract_state(cfg)
        state_shard = TS.TrainState(
            params=pshard,
            opt=type(state_shape.opt)(
                step=NamedSharding(mesh, P()),
                m=pshard, v=pshard,
            ),
        )
        batch_shape = SP.batch_specs_for(cfg, shape)
        bshard = SH.shardings_of(SH.batch_specs(cfg, batch_shape, mesh), mesh)
        n_micro = n_micro_override or SP.default_n_micro(cfg, shape, n_data)

        def step(state, batch):
            return TS.train_step.__wrapped__(cfg, state, batch, n_micro=n_micro)

        jitted = jax.jit(
            step,
            in_shardings=(state_shard, bshard),
            donate_argnums=(0,),
        )
        with mesh:
            lowered = jitted.lower(state_shape, batch_shape)
    elif shape.kind == "prefill":
        batch_shape = SP.batch_specs_for(cfg, shape)
        bshard = SH.shardings_of(SH.batch_specs(cfg, batch_shape, mesh), mesh)
        n_micro = 0

        def step(params, batch):
            return D.prefill(
                cfg, params, batch.get("tokens"),
                input_embeds=batch.get("input_embeds"),
                enc_embeds=batch.get("enc_embeds"),
                max_len=shape.seq,
            )

        jitted = jax.jit(step, in_shardings=(pshard, bshard))
        with mesh:
            lowered = jitted.lower(params_shape, batch_shape)
    else:  # decode
        from repro.launch import runtime
        runtime.set_serve_mesh(mesh)
        cache_shape, token_shape = SP.decode_inputs_for(cfg, shape)
        cshard = SH.shardings_of(SH.cache_specs(cfg, cache_shape, mesh), mesh)
        da = data_axes(mesh)
        tshard = NamedSharding(
            mesh, P(da if shape.global_batch % n_data == 0 else None))
        n_micro = 0

        def step(params, cache, token):
            return D.decode_step(cfg, params, cache, token)

        jitted = jax.jit(step, in_shardings=(pshard, cshard, tshard),
                         donate_argnums=(1,))
        with mesh:
            lowered = jitted.lower(params_shape, cache_shape, token_shape)

    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)

    mem_rec = {
        "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
        "output_bytes": getattr(mem, "output_size_in_bytes", None),
        "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
        "generated_code_bytes": getattr(mem, "generated_code_size_in_bytes", None),
        "alias_bytes": getattr(mem, "alias_size_in_bytes", None),
    }
    rec = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "multi" if multi_pod else "single",
        "status": "ok",
        "n_devices": mesh.size,
        "fsdp": bool(fsdp),
        "n_micro": n_micro,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "memory": mem_rec,
        "flops": float(cost.get("flops", -1.0)),
        "bytes_accessed": float(cost.get("bytes accessed", -1.0)),
        "collectives": coll,
        "n_params": cfg.n_params(),
        "n_active_params": cfg.n_active_params(),
    }
    if not quiet:
        print(json.dumps({k: rec[k] for k in
                          ("arch", "shape", "mesh", "status", "compile_s")}))
        print("  memory_analysis:", {k: v for k, v in mem_rec.items() if v})
        print("  cost_analysis: flops=%.3e bytes=%.3e" % (rec["flops"], rec["bytes_accessed"]))
        print("  collectives:", coll["counts"], "total_bytes=%.3e" % coll["total_bytes"])
    return rec


def run_probes(out_path: Path, archs, shapes):
    """Compile the shallow scanned/unrolled probe variants used by the
    roofline extrapolation (see repro.launch.specs.probe_variants)."""
    ledger = {}
    if out_path.exists():
        ledger = json.loads(out_path.read_text())
    for arch in archs:
        cfg = configs.get(arch)
        for shape in shapes:
            okc, _ = SP.cell_supported(cfg, shape)
            if not okc:
                continue
            kind = SP.SHAPES[shape].kind
            for i, (variant, coeffs) in enumerate(SP.probe_variants(cfg, kind)):
                key = f"{arch}|{shape}|probe{i}"
                if ledger.get(key, {}).get("status") == "ok":
                    continue
                try:
                    rec = run_cell(arch, shape, False, cfg_override=variant,
                                   n_micro_override=1, quiet=True)
                    rec["coeffs"] = coeffs
                    print(f"probe ok {key} flops={rec['flops']:.3e}")
                except Exception as e:
                    rec = {"status": "error", "error": f"{type(e).__name__}: {e}",
                           "coeffs": coeffs}
                    print(f"probe FAILED {key}: {e}", file=sys.stderr)
                ledger[key] = rec
                out_path.write_text(json.dumps(ledger, indent=1))
    return ledger


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="both")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun.json")
    ap.add_argument("--fsdp", default=None, choices=[None, "on", "off"])
    ap.add_argument("--probes", action="store_true",
                    help="run roofline probe variants instead of full cells")
    args = ap.parse_args()

    out_path = Path(args.out)
    out_path.parent.mkdir(parents=True, exist_ok=True)

    if args.probes:
        archs = configs.ARCH_NAMES if (args.all or not args.arch) else [args.arch]
        shapes = list(SP.SHAPES) if (args.all or not args.shape) else [args.shape]
        run_probes(out_path, archs, shapes)
        return 0
    ledger: dict[str, dict] = {}
    if out_path.exists():
        ledger = json.loads(out_path.read_text())

    archs = configs.ARCH_NAMES if (args.all or not args.arch) else [args.arch]
    shapes = list(SP.SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    fsdp = None if args.fsdp is None else (args.fsdp == "on")

    failures = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                key = f"{arch}|{shape}|{'multi' if mp else 'single'}"
                if ledger.get(key, {}).get("status") in ("ok", "skipped"):
                    continue
                try:
                    rec = run_cell(arch, shape, mp, fsdp=fsdp)
                except Exception as e:
                    failures += 1
                    rec = {
                        "arch": arch, "shape": shape,
                        "mesh": "multi" if mp else "single",
                        "status": "error",
                        "error": f"{type(e).__name__}: {e}",
                        "trace": traceback.format_exc()[-2000:],
                    }
                    print(f"FAILED {key}: {type(e).__name__}: {e}", file=sys.stderr)
                ledger[key] = rec
                out_path.write_text(json.dumps(ledger, indent=1))
    print(f"dry-run complete: {sum(1 for r in ledger.values() if r['status']=='ok')} ok, "
          f"{sum(1 for r in ledger.values() if r['status']=='skipped')} skipped, "
          f"{sum(1 for r in ledger.values() if r['status']=='error')} errors")
    return 0 if failures == 0 else 1


if __name__ == "__main__":
    sys.exit(main())
