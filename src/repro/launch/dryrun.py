import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture × shape × mesh)
cell against the production mesh, with NO device allocation
(ShapeDtypeStruct inputs), and record memory/cost/roofline terms.

The two lines above MUST precede every other import — jax locks the device
count at first initialization.

Usage:
    python -m repro.launch.dryrun --arch vit-l16 --shape serve_b1
    python -m repro.launch.dryrun --all --mesh single --out results/
    python -m repro.launch.dryrun --all --mesh multi
"""
import argparse          # noqa: E402
import json              # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402
from pathlib import Path  # noqa: E402

import jax               # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.compile_cache import use_compile_cache  # noqa: E402
from repro.configs import all_cells, get_config, shapes_for  # noqa: E402
from repro.distributed import sharding as shd  # noqa: E402
from repro.launch import roofline  # noqa: E402
from repro.launch.mesh import install_rules, make_production_mesh  # noqa: E402
from repro.launch.steps import build_cell  # noqa: E402


def _axis_prod(mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        return mesh.shape[axes]
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def _to_shardings(mesh, logical_tree, spec_tree):
    """Logical tuples -> NamedShardings, dropping (replicating) any axis
    whose size does not divide the corresponding dim — jit input shardings
    must divide evenly (GSPMD handles uneven shardings only on
    intermediates)."""
    def leaf(names, spec):
        if not isinstance(names, tuple):
            return NamedSharding(mesh, P())
        resolved = shd.logical(*names)
        fixed = []
        for i, axes in enumerate(resolved):
            if axes is None or i >= len(spec.shape) or \
                    spec.shape[i] % _axis_prod(mesh, axes) != 0:
                fixed.append(None)
            else:
                fixed.append(axes)
        return NamedSharding(mesh, P(*fixed))

    return jax.tree_util.tree_map(
        leaf, logical_tree, spec_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            a is None or isinstance(a, str) for a in x))


def _parse_override(val: str):
    if val in ("true", "True"):
        return True
    if val in ("false", "False"):
        return False
    if val in ("none", "None"):
        return None
    try:
        return int(val)
    except ValueError:
        pass
    try:
        return float(val)
    except ValueError:
        return val


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             donate: bool = True, overrides: dict = None,
             rule_overrides: dict = None) -> dict:
    import dataclasses
    from repro.configs import get_config
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.size
    cfg = get_config(arch)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    cell = build_cell(arch, shape_name, cfg=cfg)
    rules = install_rules(mesh, cell.cfg, cell.shape.global_batch,
                          kind=cell.shape.kind)
    if rule_overrides:
        rules.update(rule_overrides)
        shd.set_rules(mesh=mesh, **rules)
    in_shardings = _to_shardings(mesh, cell.arg_logical, cell.arg_specs)

    t0 = time.time()
    with mesh:
        jitted = jax.jit(cell.step_fn, in_shardings=in_shardings,
                         donate_argnums=cell.donate if donate else ())
        lowered = jitted.lower(*cell.arg_specs)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        mem = compiled.memory_analysis()
        terms = roofline.analyze(compiled, cell.cfg, cell.shape, chips)
        raw_cost = compiled.cost_analysis()
        if isinstance(raw_cost, list):
            raw_cost = raw_cost[0]

    result = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": chips,
        "overrides": overrides or {},
        "rule_overrides": {k: list(v) if isinstance(v, tuple) else v
                           for k, v in (rule_overrides or {}).items()},
        "rules": {k: list(v) if isinstance(v, tuple) else v
                  for k, v in rules.items()},
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "code_bytes": getattr(mem, "generated_code_size_in_bytes", None),
        },
        "flops_per_chip": terms.flops,
        "hbm_bytes_per_chip": terms.hbm_bytes,
        "collective_bytes_per_chip": terms.coll_bytes,
        "collective_breakdown": terms.coll_breakdown,
        "model_flops": terms.model_flops,
        "roofline": terms.summary(),
        # raw XLA numbers for reference (while bodies counted once)
        "xla_cost_analysis": {"flops": float(raw_cost.get("flops", 0.0)),
                              "bytes": float(raw_cost.get("bytes accessed",
                                                          0.0))},
        "status": "ok",
    }
    shd.clear_rules()
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--override", action="append", default=[],
                    help="cfg field override key=value (hillclimb iterations)")
    ap.add_argument("--rule", action="append", default=[],
                    help="logical rule override key=value, e.g. dp=data,model")
    ap.add_argument("--tag", default="",
                    help="suffix for the result file name")
    args = ap.parse_args()
    use_compile_cache()
    overrides = {}
    for kv in args.override:
        k, v = kv.split("=", 1)
        overrides[k] = _parse_override(v)
    rule_overrides = {}
    for kv in args.rule:
        k, v = kv.split("=", 1)
        parts = tuple(p for p in v.split(",") if p)
        rule_overrides[k] = (parts[0] if len(parts) == 1 else parts) \
            if parts else None

    outdir = Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)

    if args.all:
        cells, skips = all_cells()
        for arch, shape, why in skips:
            print(f"SKIP {arch}:{shape} — {why}")
            (outdir / f"{arch}__{shape}__skip.json").write_text(
                json.dumps({"arch": arch, "shape": shape,
                            "status": "skipped", "reason": why}, indent=1))
    else:
        cells = [(args.arch, args.shape)]

    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    n_fail = 0
    for arch, shape in cells:
        for multi in meshes:
            tag = f"{arch}__{shape}__{'multi' if multi else 'single'}"
            if args.tag:
                tag += f"__{args.tag}"
            path = outdir / f"{tag}.json"
            if args.skip_existing and path.exists():
                prev = json.loads(path.read_text())
                if prev.get("status") == "ok":
                    print(f"SKIP (cached) {tag}")
                    continue
            print(f"=== {tag} ===", flush=True)
            try:
                res = run_cell(arch, shape, multi, overrides=overrides,
                               rule_overrides=rule_overrides)
                rf = res["roofline"]
                print(f"  ok: compile={res['compile_s']}s "
                      f"bottleneck={rf['bottleneck']} "
                      f"t=(c {rf['t_compute_s']:.4f}, m {rf['t_memory_s']:.4f}, "
                      f"x {rf['t_collective_s']:.4f})s "
                      f"useful={rf['useful_flops_ratio']:.2f}", flush=True)
            except Exception as e:
                n_fail += 1
                res = {"arch": arch, "shape": shape,
                       "mesh": "2x16x16" if multi else "16x16",
                       "status": "failed", "error": f"{type(e).__name__}: {e}",
                       "traceback": traceback.format_exc()[-3000:]}
                print(f"  FAILED: {type(e).__name__}: {str(e)[:300]}",
                      flush=True)
            path.write_text(json.dumps(res, indent=1, default=str))
    if n_fail:
        raise SystemExit(f"{n_fail} cells failed")


if __name__ == "__main__":
    main()
