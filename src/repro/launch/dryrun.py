import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input-shape x
mesh) cell with 512 placeholder host devices; record memory analysis, cost
analysis and roofline terms (EXPERIMENTS.md reads the JSON reports).

  PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all
  PYTHONPATH=src python -m repro.launch.dryrun --arch deepseek-v2-236b \
      --shape train_4k --multi-pod
"""
import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro.analysis.roofline import analyze_compiled
from repro.configs import ARCHS, SHAPES
from repro.launch.mesh import make_production_mesh
from repro.models import zoo
from repro.models.module import abstract_from_specs
from repro.sharding.rules import sharding_for, tree_shardings
from repro.train.optimizer import AdamWConfig
from repro.train.train_step import (TrainStepConfig, make_train_step,
                                    train_state_specs)

# logical axes of each data input
_BATCH_AXES = {
    "tokens": ("batch", None),
    "labels": ("batch", None),
    "enc_embeds": ("batch", None, None),
    "enc_out": ("batch", None, None),
    "mrope_positions": (None, "batch", None),
    "cur_len": None,
}


def batch_shardings(batch_specs, mesh):
    return {k: sharding_for(_BATCH_AXES.get(k), v.shape, mesh)
            for k, v in batch_specs.items()}


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool,
               step_cfg: TrainStepConfig | None = None, mesh=None):
    """Build + lower + compile one cell; returns (compiled, report dict)."""
    cfg = ARCHS[arch]
    shape = SHAPES[shape_name]
    ok, why = cfg.supports_shape(shape)
    if not ok:
        return None, dict(arch=arch, shape=shape_name, skipped=True, why=why)

    mesh = mesh or make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    step_cfg = step_cfg or TrainStepConfig(remat=True, opt=AdamWConfig())

    pspecs = zoo.build_param_specs(cfg)
    params_abs = abstract_from_specs(pspecs)
    params_sh = tree_shardings(pspecs, mesh)
    data_specs = zoo.input_specs(cfg, shape)
    data_sh = batch_shardings(data_specs, mesh)
    t0 = time.perf_counter()

    with jax.set_mesh(mesh):
        if shape.kind == "train":
            sspecs = train_state_specs(pspecs, step_cfg)
            state_abs = abstract_from_specs(sspecs)
            state_sh = tree_shardings(sspecs, mesh)
            fn = make_train_step(cfg, mesh, step_cfg)
            jfn = jax.jit(fn, in_shardings=(params_sh, state_sh, data_sh),
                          out_shardings=(params_sh, state_sh, None),
                          donate_argnums=(0, 1))
            lowered = jfn.lower(params_abs, state_abs, data_specs)
        elif shape.kind == "prefill":
            cspecs = zoo.build_cache_specs(cfg, shape.global_batch,
                                           shape.seq_len)
            caches_abs = abstract_from_specs(cspecs)
            caches_sh = tree_shardings(cspecs, mesh)

            def prefill_fn(params, batch, caches):
                return zoo.prefill(cfg, params, batch, caches, mesh=mesh)

            jfn = jax.jit(prefill_fn,
                          in_shardings=(params_sh, data_sh, caches_sh),
                          out_shardings=(None, caches_sh),
                          donate_argnums=(2,))
            lowered = jfn.lower(params_abs, data_specs, caches_abs)
        else:  # decode
            cspecs = zoo.build_cache_specs(cfg, shape.global_batch,
                                           shape.seq_len)
            caches_abs = abstract_from_specs(cspecs)
            caches_sh = tree_shardings(cspecs, mesh)
            tok_spec = data_specs["tokens"]
            len_spec = data_specs["cur_len"]
            enc_spec = data_specs.get("enc_out")

            def serve_step(params, tokens, caches, cur_len, enc_out=None):
                return zoo.decode_step(cfg, params, tokens, caches, cur_len,
                                       mesh=mesh, enc_out=enc_out)

            args = [params_abs, tok_spec, caches_abs, len_spec]
            in_sh = [params_sh, data_sh["tokens"], caches_sh,
                     data_sh["cur_len"]]
            if enc_spec is not None:
                args.append(enc_spec)
                in_sh.append(data_sh["enc_out"])
            jfn = jax.jit(serve_step, in_shardings=tuple(in_sh),
                          out_shardings=(None, caches_sh),
                          donate_argnums=(2,))
            lowered = jfn.lower(*args)

        t_lower = time.perf_counter() - t0
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0 - t_lower

    try:
        mem = compiled.memory_analysis()
        mem_report = {
            "argument_size_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_size_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_size_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_size_bytes": getattr(
                mem, "generated_code_size_in_bytes", None),
        }
    except Exception as e:  # CPU backend may not expose memory analysis
        mem_report = {"error": str(e)}

    roof = analyze_compiled(compiled, zoo.model_flops(cfg, shape), chips)
    report = dict(
        arch=arch, shape=shape_name, mesh="x".join(map(str, mesh.devices.shape)),
        multi_pod=multi_pod, chips=chips, kind=shape.kind,
        lower_s=round(t_lower, 2), compile_s=round(t_compile, 2),
        memory=mem_report, roofline=roof.summary(), skipped=False,
    )
    return compiled, report


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="reports/dryrun")
    args = ap.parse_args()

    archs = list(ARCHS) if args.arch == "all" else args.arch.split(",")
    shapes = list(SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    os.makedirs(args.out, exist_ok=True)
    failures = []
    for multi_pod in meshes:
        mesh = make_production_mesh(multi_pod=multi_pod)
        tag = "2x16x16" if multi_pod else "16x16"
        for arch in archs:
            for shape in shapes:
                cell = f"{tag}/{arch}/{shape}"
                path = os.path.join(args.out, tag, arch)
                os.makedirs(path, exist_ok=True)
                fname = os.path.join(path, f"{shape}.json")
                t0 = time.perf_counter()
                try:
                    compiled, report = lower_cell(
                        arch, shape, multi_pod=multi_pod, mesh=mesh)
                    del compiled
                except Exception as e:
                    report = dict(arch=arch, shape=shape, mesh=tag,
                                  failed=True, error=str(e),
                                  traceback=traceback.format_exc())
                    failures.append(cell)
                with open(fname, "w") as f:
                    json.dump(report, f, indent=1, default=str)
                dt = time.perf_counter() - t0
                if report.get("skipped"):
                    print(f"[SKIP] {cell}: {report['why']}", flush=True)
                elif report.get("failed"):
                    print(f"[FAIL] {cell}: {report['error']}", flush=True)
                else:
                    r = report["roofline"]
                    print(f"[ OK ] {cell}: {dt:.0f}s "
                          f"bottleneck={r['bottleneck']} "
                          f"t=({r['t_compute_s']:.2e},{r['t_memory_s']:.2e},"
                          f"{r['t_collective_s']:.2e})s "
                          f"useful={r['useful_flops_ratio']:.2f} "
                          f"mfu={r['mfu']:.2f}", flush=True)
    if failures:
        print(f"\n{len(failures)} FAILURES: {failures}", flush=True)
        raise SystemExit(1)
    print("\nall dry-run cells passed", flush=True)


if __name__ == "__main__":
    main()
