import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512 " + os.environ.get("XLA_FLAGS", "")
# ^ The two lines above MUST stay first — before any other import — because
#   jax locks the device count at first initialization.

__doc__ = """Multi-pod dry-run: lower + compile every (arch x shape) cell on
the production meshes and record memory/cost/collective statistics.

    PYTHONPATH=src python -m repro.launch.dryrun --arch fm --shape train_batch
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both

Results are cached as JSON under ``<checkout>/.dryrun_cache/`` (created
on demand, git-ignored) keyed by (arch, shape, mesh); EXPERIMENTS.md §Dry-run
and §Roofline are generated from these files.
"""

import argparse
import json
import time
import traceback

import jax

from repro import configs as cfg_lib
from repro.launch.mesh import make_debug_mesh, make_production_mesh
from repro.roofline import analysis

RESULTS_DIR = os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".dryrun_cache"
)


import dataclasses as _dc

# §Perf variants: config transforms applied to LM cells via --variant.
_VARIANTS = {
    # iteration 1 (MoE): shard_map replicated-dispatch EP (kills the MoE
    # dispatch all-reduces — see models/moe.moe_ffn_shard_map)
    "moe_sm": lambda cfg: _dc.replace(cfg, moe_shard_map=True),
    # iteration 2 (dense LM, memory): bf16 score/softmax chain
    "attn_bf16": lambda cfg: _dc.replace(cfg, attn_softmax_dtype="bf16"),
    # iteration 3 (dense LM, memory): keep matmul outputs, recompute the rest
    "remat_dots": lambda cfg: _dc.replace(cfg, remat_policy="dots"),
    # iteration 4 (dense LM, memory): lean norms + bf16 CE chain
    "mem_lean": lambda cfg: _dc.replace(cfg, mem_lean=True),
    # combined memory variant
    "mem_opt": lambda cfg: _dc.replace(
        cfg, attn_softmax_dtype="bf16", remat_policy="dots", mem_lean=True
    ),
    # MoE combined: shard_map dispatch + lean memory
    "moe_sm2": lambda cfg: _dc.replace(
        cfg, moe_shard_map=True, attn_softmax_dtype="bf16", mem_lean=True
    ),
}


def _variant_cfg(arch: str, variant: str):
    cfg = cfg_lib.get_module(arch).CONFIG
    return _VARIANTS[variant](cfg) if variant else cfg


def _calib_cell(arch: str, shape_id: str, depth: int, variant: str = ""):
    """Depth-override variant (unrolled python loop over layers) used for the
    two-point cost extrapolation: scan bodies are cost-analysed once per
    program, so we compile depth-(d+1) and depth-(d+2) unrolled variants and
    reconstruct  total = entry + L_scan * body  exactly (layers are
    homogeneous).  See roofline/analysis.extrapolate_depth."""
    import dataclasses

    from repro.configs import base as cfg_base

    cfg = _variant_cfg(arch, variant)
    new_cfg = dataclasses.replace(
        cfg, n_layers=cfg.first_dense_layers + depth, unroll=True
    )
    return cfg_base.lm_cells(arch, new_cfg)[shape_id]()


def run_cell(
    arch: str,
    shape_id: str,
    *,
    multi_pod: bool,
    debug: bool = False,
    calib_depth: int = 0,
    variant: str = "",
):
    """Lower + compile one cell; returns the result record dict."""
    mesh = (make_debug_mesh if debug else make_production_mesh)(multi_pod=multi_pod)
    if calib_depth or variant:
        from repro.configs import base as cfg_base

        cfg = _variant_cfg(arch, variant)
        if calib_depth:
            cell = _calib_cell(arch, shape_id, calib_depth, variant)
        else:
            cell = cfg_base.lm_cells(arch, cfg)[shape_id]()
    else:
        cell = cfg_lib.build_cell(arch, shape_id)
    record = {
        "arch": arch,
        "shape": shape_id,
        "kind": cell.kind,
        "mesh": "x".join(str(s) for s in mesh.devices.shape),
        "axes": list(mesh.axis_names),
        "note": cell.note,
        "variant": variant,
    }
    t0 = time.time()
    from repro.distributed.sharding import sanitize_shardings

    in_sh = sanitize_shardings(cell.in_shardings(mesh), cell.abstract_args)
    # set_mesh (not a bare `with mesh:`) so shard_map variants can resolve
    # the ambient mesh at trace time.
    with jax.sharding.set_mesh(mesh):
        jitted = jax.jit(
            cell.step_fn,
            in_shardings=in_sh,
            donate_argnums=cell.donate_argnums,
        )
        lowered = jitted.lower(*cell.abstract_args)
        record["lower_s"] = round(time.time() - t0, 2)

        t1 = time.time()
        compiled = lowered.compile()
        record["compile_s"] = round(time.time() - t1, 2)

    # --- memory ----------------------------------------------------------
    try:
        mem = compiled.memory_analysis()
        record["memory"] = {
            "argument_size_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
            "output_size_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
            "temp_size_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
            "generated_code_size_bytes": int(
                getattr(mem, "generated_code_size_in_bytes", 0)
            ),
        }
    except Exception as exc:  # CPU backend may not expose everything
        record["memory"] = {"error": repr(exc)}

    # --- cost ------------------------------------------------------------
    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        record["cost"] = {
            "flops": float(cost.get("flops", 0.0)),
            "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
            "transcendentals": float(cost.get("transcendentals", 0.0)),
        }
    except Exception as exc:
        record["cost"] = {"error": repr(exc)}

    # --- collectives (parsed from the partitioned HLO) --------------------
    try:
        hlo = compiled.as_text()
        record["collectives"] = analysis.collective_bytes(hlo)
        record["hlo_ops"] = analysis.op_histogram(hlo)
    except Exception as exc:
        record["collectives"] = {"error": repr(exc)}

    return record


def result_path(
    arch: str, shape_id: str, multi_pod: bool, calib_depth: int = 0,
    variant: str = "",
) -> str:
    tag = "multipod" if multi_pod else "singlepod"
    if variant:
        tag += f"__v-{variant}"
    if calib_depth:
        tag += f"__calib{calib_depth}"
    safe = arch.replace("/", "_").replace(".", "_")
    return os.path.abspath(
        os.path.join(RESULTS_DIR, f"{safe}__{shape_id}__{tag}.json")
    )


def _is_lm_arch(arch: str) -> bool:
    from repro.models.transformer import TransformerConfig

    return isinstance(cfg_lib.get_module(arch).CONFIG, TransformerConfig)


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--arch", default=None)
    parser.add_argument("--shape", default=None)
    parser.add_argument("--all", action="store_true")
    parser.add_argument(
        "--mesh", choices=["single", "multi", "both"], default="both"
    )
    parser.add_argument("--debug-mesh", action="store_true",
                        help="2x2(x2) mesh for fast checks")
    parser.add_argument("--force", action="store_true", help="ignore cache")
    parser.add_argument("--include-dpmf", action="store_true", default=True)
    parser.add_argument("--variant", default="",
                        choices=[""] + sorted(_VARIANTS),
                        help="apply a §Perf config variant to LM cells")
    parser.add_argument(
        "--calib",
        action="store_true",
        help="also compile unrolled depth-1/2 variants of LM cells for exact "
        "cost extrapolation (roofline)",
    )
    args = parser.parse_args()

    if args.all:
        targets = cfg_lib.all_cells(include_dpmf=args.include_dpmf)
    elif args.arch and args.shape:
        targets = [(args.arch, args.shape)]
    elif args.arch:
        targets = [(args.arch, sid) for sid in cfg_lib.shape_ids(args.arch)]
    else:
        parser.error("pass --all or --arch [--shape]")

    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    os.makedirs(RESULTS_DIR, exist_ok=True)

    failures = 0
    runs = []
    for arch, shape_id in targets:
        for multi_pod in meshes:
            runs.append((arch, shape_id, multi_pod, 0))
            if args.calib and _is_lm_arch(arch):
                runs.append((arch, shape_id, multi_pod, 1))
                runs.append((arch, shape_id, multi_pod, 2))

    for arch, shape_id, multi_pod, depth in runs:
        path = result_path(arch, shape_id, multi_pod, depth, args.variant)
        tag = f"{arch}::{shape_id} multi_pod={multi_pod}" + (
            f" calib={depth}" if depth else ""
        ) + (f" variant={args.variant}" if args.variant else "")
        if not args.force and os.path.exists(path) and not args.debug_mesh:
            print(f"[cached] {tag}")
            continue
        print(f"[run]    {tag}", flush=True)
        try:
            record = run_cell(
                arch,
                shape_id,
                multi_pod=multi_pod,
                debug=args.debug_mesh,
                calib_depth=depth,
                variant=args.variant,
            )
            record["status"] = "ok"
            record["calib_depth"] = depth
        except Exception as exc:  # noqa: BLE001 — report and continue
            failures += 1
            record = {
                "arch": arch,
                "shape": shape_id,
                "mesh": "multi" if multi_pod else "single",
                "status": "error",
                "calib_depth": depth,
                "error": repr(exc),
                "traceback": traceback.format_exc(),
            }
            print(f"[FAIL]   {tag}: {exc!r}", flush=True)
        if not args.debug_mesh:
            with open(path, "w") as f:
                json.dump(record, f, indent=2)
        if record["status"] == "ok":
            flops = record.get("cost", {}).get("flops", 0)
            coll = record.get("collectives", {}).get("total_bytes", 0)
            print(
                f"[ok]     {tag} lower={record['lower_s']}s "
                f"compile={record['compile_s']}s flops={flops:.3e} "
                f"coll_bytes={coll:.3e}",
                flush=True,
            )
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
