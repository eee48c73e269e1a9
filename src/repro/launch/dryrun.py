import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: prove every (arch x shape x mesh) lowers + compiles.

The two lines above MUST run before any jax import (jax locks the device
count at first init); 512 placeholder host devices back the production
meshes. Usage:

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-0.5b \
        --shape train_4k --mesh single
    PYTHONPATH=src python -m repro.launch.dryrun --all --out dryrun.jsonl

Per cell it records the compile time and compiled.memory_analysis(): the
per-device live bytes and whether they fit one chip's HBM. Speed is not
estimated here; it is measured on the chip by bench/run.py.
"""
import argparse
import json
import sys
import time
import traceback

import jax
import numpy as np

from repro.configs import get_config, list_archs
from repro.launch.mesh import make_production_mesh
from repro.models import SHAPES, build, shape_applicable
from repro.optim import get_optimizer
from repro.runtime.sharding import ShardingRules
from repro.runtime.steps import make_decode_step, make_prefill_step, make_train_step

HBM_PER_CHIP = 16 << 30  # v5e: 16 GiB


def _tokens_of(cfg, shape_name: str) -> int:
    info = SHAPES[shape_name]
    if info["kind"] == "train" or info["kind"] == "prefill":
        return info["seq_len"] * info["global_batch"]
    return info["global_batch"]  # decode: one token per sequence


def memory_summary(compiled) -> dict:
    """Per-device memory from compiled.memory_analysis() (best effort)."""
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return {}
    out = {}
    for k in (
        "argument_size_in_bytes",
        "output_size_in_bytes",
        "temp_size_in_bytes",
        "alias_size_in_bytes",
        "generated_code_size_in_bytes",
    ):
        v = getattr(ma, k, None)
        if v is not None:
            out[k] = int(v)
    if out:
        # live bytes: args + outputs + temps - aliased (donated) buffers
        out["total_bytes"] = (
            out.get("argument_size_in_bytes", 0)
            + out.get("output_size_in_bytes", 0)
            + out.get("temp_size_in_bytes", 0)
            - out.get("alias_size_in_bytes", 0)
        )
    return out


def run_cell(
    arch: str,
    shape_name: str,
    mesh_kind: str,
    *,
    fsdp: bool = True,
    overrides: dict | None = None,
) -> dict:
    cfg = get_config(arch)
    if overrides:
        cfg = cfg.with_overrides(**overrides)
    ok, why = shape_applicable(cfg, shape_name)
    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "family": cfg.family, "status": "", "detail": "",
    }
    if not ok:
        rec.update(status="skip", detail=why)
        return rec

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    chips = int(np.prod(list(mesh.shape.values())))
    model = build(cfg)
    rules = ShardingRules(cfg=cfg, mesh=mesh, fsdp=fsdp)
    info = SHAPES[shape_name]
    kind = info["kind"]

    def _with_sh(abs_tree, sh_tree):
        # attach shardings to ShapeDtypeStructs so lowering sees the real
        # data layout (otherwise XLA replicates the batch => 256x the work)
        return jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            abs_tree, sh_tree,
        )

    t0 = time.perf_counter()
    with jax.set_mesh(mesh):
        if kind == "train":
            jitted, state_sh, batch_sh_fn = make_train_step(
                model, rules, get_optimizer(cfg.optimizer, 1e-4)
            )
            specs = model.input_specs(shape_name)
            specs = _with_sh(specs, batch_sh_fn(specs))
            params_shape = jax.eval_shape(lambda: model.init(jax.random.key(0)))
            opt_shape = jax.eval_shape(
                lambda: get_optimizer(cfg.optimizer, 1e-4).init(params_shape)
            )
            state_abs = {
                "params": params_shape,
                "opt": opt_shape,
                "step": jax.ShapeDtypeStruct((), np.int32),
            }
            lowered = jitted.lower(state_abs, specs)
        elif kind == "prefill":
            jitted, p_sh = make_prefill_step(model, rules, info["seq_len"])
            specs = model.input_specs(shape_name)
            specs = _with_sh(
                specs,
                jax.tree.map(
                    lambda l: rules.batch_sharding_for(tuple(l.shape)), specs
                ),
            )
            params_shape = jax.eval_shape(lambda: model.init(jax.random.key(0)))
            lowered = jitted.lower(params_shape, specs)
        else:  # decode
            jitted, p_sh, cache_sh_fn, tok_sh = make_decode_step(model, rules)
            specs = model.input_specs(shape_name)
            cache_abs = _with_sh(specs["cache"], cache_sh_fn(specs["cache"]))
            tok_abs = _with_sh(specs["tokens"], tok_sh(specs["tokens"]))
            params_shape = jax.eval_shape(lambda: model.init(jax.random.key(0)))
            lowered = jitted.lower(params_shape, cache_abs, tok_abs)

        compiled = lowered.compile()

    mem = memory_summary(compiled)
    rec.update(
        status="ok",
        compile_s=round(time.perf_counter() - t0, 1),
        chips=chips,
        n_params=cfg.n_params(),
        active_params=cfg.active_params_per_token(),
        tokens=_tokens_of(cfg, shape_name),
        memory=mem,
        per_device_bytes=mem.get("total_bytes"),
        fits_hbm=(mem.get("total_bytes", 0) <= HBM_PER_CHIP) if mem else None,
    )
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=list_archs())
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="single")
    ap.add_argument("--all", action="store_true", help="run every cell")
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--out", help="append JSONL records here")
    ap.add_argument("--override", action="append", default=[],
                    help="cfg override k=v (e.g. microbatches=4)")
    args = ap.parse_args(argv)

    overrides = {}
    for kv in args.override:
        k, v = kv.split("=", 1)
        try:
            v = json.loads(v)
        except json.JSONDecodeError:
            pass
        overrides[k] = v

    cells = []
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    if args.all:
        for a in list_archs():
            for s in SHAPES:
                for mk in meshes:
                    cells.append((a, s, mk))
    else:
        if not (args.arch and args.shape):
            ap.error("--arch and --shape required unless --all")
        cells = [(args.arch, args.shape, mk) for mk in meshes]

    failures = 0
    for arch, shape, mk in cells:
        try:
            rec = run_cell(arch, shape, mk, fsdp=not args.no_fsdp,
                           overrides=overrides or None)
        except Exception as e:
            rec = {
                "arch": arch, "shape": shape, "mesh": mk, "status": "fail",
                "detail": f"{type(e).__name__}: {e}",
                "trace": traceback.format_exc()[-2000:],
            }
            failures += 1
        line = json.dumps(rec)
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
