import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# The two lines above MUST run before any jax import anywhere: jax locks the
# device count at first init, and the dry-run needs 512 placeholder host
# devices to build the production meshes.  (Only the dry-run: smoke tests and
# benches see the real single device.)

import argparse      # noqa: E402
import dataclasses   # noqa: E402
import json          # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

import jax           # noqa: E402

from repro.configs.base import ARCH_IDS, SHAPES, get_config  # noqa: E402
from repro.launch.mesh import make_production_mesh           # noqa: E402
from repro.launch.steps import bundle_for                    # noqa: E402
from repro.roofline import analysis as RL                    # noqa: E402


def _lower_compile(cfg, shape_name, mesh):
    bundle = bundle_for(cfg, shape_name, mesh)
    jitted = jax.jit(bundle.fn,
                     in_shardings=bundle.in_shardings,
                     out_shardings=bundle.out_shardings,
                     donate_argnums=bundle.donate)
    with mesh:
        lowered = jitted.lower(*bundle.abstract_args)
        compiled = lowered.compile()
    return compiled


def _depth_variant(cfg, n_groups: int):
    """Unrolled shallow variant with the same per-group structure + remat
    (used for per-layer cost extrapolation; see RL.extrapolate_costs)."""
    from repro.models.transformer import group_layout
    layers_per_group = len(group_layout(cfg)[1])
    return dataclasses.replace(cfg, n_layers=layers_per_group * n_groups,
                               scan_layers=False)


def extrapolated_costs(cfg, shape_name, mesh):
    """(cost_dict, coll_by_type) for the full-depth program, built from
    unrolled 1-group / 2-group lowers (scan bodies are otherwise counted
    once by cost_analysis)."""
    from repro.models.transformer import group_layout
    n_groups = group_layout(cfg)[0]
    c = [None, None]
    coll = [None, None]
    for i, g in enumerate((1, 2)):
        comp = _lower_compile(_depth_variant(cfg, g), shape_name, mesh)
        c[i] = comp.cost_analysis() or {}
        coll[i] = RL.collective_bytes(comp.as_text())
    return RL.extrapolate_costs(c[0], c[1], coll[0], coll[1], n_groups)


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             out_dir: str = None, verbose: bool = True,
             cfg=None, tag: str = "") -> dict:
    """Lower + compile one (arch x shape x mesh) cell; return the record."""
    cfg = cfg or get_config(arch)
    if shape_name not in cfg.shapes():
        return {"arch": arch, "shape": shape_name, "skipped": True,
                "reason": "shape not eligible for this arch (DESIGN.md §5)"}
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    t0 = time.time()

    bundle = bundle_for(cfg, shape_name, mesh)
    jitted = jax.jit(bundle.fn,
                     in_shardings=bundle.in_shardings,
                     out_shardings=bundle.out_shardings,
                     donate_argnums=bundle.donate)
    with mesh:
        lowered = jitted.lower(*bundle.abstract_args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost_raw = compiled.cost_analysis() or {}
    hlo = compiled.as_text()

    # scan bodies are counted once by cost_analysis — extrapolate the true
    # full-depth cost from unrolled 1-/2-group variants.
    cost, coll = extrapolated_costs(cfg, shape_name, mesh)
    # ... and the gradient-accumulation scan body is likewise counted once:
    # scale flops/bytes/collectives by the microbatch count.
    A = max(1, cfg.microbatches)
    if A > 1 and SHAPES[shape_name].kind == "train":
        cost = {k: v * A for k, v in cost.items()
                if isinstance(v, (int, float))}
        coll = {k: v * A for k, v in coll.items()}

    shape = SHAPES[shape_name]
    rl = RL.from_costs(
        f"{arch}/{shape_name}/{mesh_name}" + (f"/{tag}" if tag else ""),
        chips=mesh.size,
        cost=cost,
        coll_by_type=coll,
        model_flops=RL.model_flops_for(cfg, shape),
        peak_memory_bytes=_peak_bytes(mem),
        device_kind=RL.V5E)

    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "chips": mesh.size, "skipped": False, "tag": tag,
        "seconds_lower": round(t_lower, 1),
        "seconds_compile": round(t_compile, 1),
        "memory_analysis": _mem_dict(mem),
        "cost_analysis": {k: v for k, v in cost.items()
                          if isinstance(v, (int, float))},
        "cost_analysis_raw_scanned": {k: v for k, v in cost_raw.items()
                                      if isinstance(v, (int, float))},
        "roofline": rl.row(),
        "hlo_bytes": len(hlo),
        "n_collectives": sum(
            hlo.count(f" {op}(") + hlo.count(f" {op}-start(")
            for op in ("all-reduce", "all-gather", "reduce-scatter",
                       "all-to-all", "collective-permute")),
    }
    if verbose:
        print(f"[dryrun] {arch:24s} {shape_name:12s} {mesh_name:10s} "
              f"compile={t_compile:6.1f}s "
              f"mem/dev={rec['memory_analysis'].get('temp_gb', -1):.2f}GB "
              f"bottleneck={rl.bottleneck}", flush=True)
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        fname = f"{arch}__{shape_name}__{mesh_name}"
        if tag:
            fname += f"__{tag}"
        path = os.path.join(out_dir, fname + ".json")
        with open(path, "w") as f:
            json.dump(rec, f, indent=1, default=float)
    return rec


def _peak_bytes(mem) -> float:
    for attr in ("temp_size_in_bytes",):
        if hasattr(mem, attr):
            t = getattr(mem, attr)
            args = getattr(mem, "argument_size_in_bytes", 0)
            out = getattr(mem, "output_size_in_bytes", 0)
            alias = getattr(mem, "alias_size_in_bytes", 0)
            return float(t + args + out - alias)
    return 0.0


def _mem_dict(mem) -> dict:
    g = 2.0 ** 30
    d = {}
    for attr, key in (("argument_size_in_bytes", "args_gb"),
                      ("output_size_in_bytes", "out_gb"),
                      ("temp_size_in_bytes", "temp_gb"),
                      ("alias_size_in_bytes", "alias_gb"),
                      ("generated_code_size_in_bytes", "code_gb")):
        if hasattr(mem, attr):
            d[key] = round(getattr(mem, attr) / g, 3)
    d["total_gb"] = round(_peak_bytes(mem) / g, 3)
    return d


def main() -> None:
    ap = argparse.ArgumentParser(description="multi-pod dry-run")
    ap.add_argument("--arch", default="all",
                    help="arch id or 'all'")
    ap.add_argument("--shape", default="all",
                    help="shape name or 'all'")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--tag", default="", help="suffix records (e.g. 'opt')")
    args = ap.parse_args()

    archs = ARCH_IDS if args.arch == "all" else args.arch.split(",")
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    n_ok = n_skip = n_fail = 0
    for arch in archs:
        cfg = get_config(arch)
        shapes = cfg.shapes() if args.shape == "all" else [args.shape]
        for shape_name in shapes:
            for multi in meshes:
                try:
                    rec = run_cell(arch, shape_name, multi, args.out,
                                   tag=args.tag)
                    if rec.get("skipped"):
                        n_skip += 1
                    else:
                        n_ok += 1
                except Exception:
                    n_fail += 1
                    print(f"[dryrun] FAIL {arch} {shape_name} "
                          f"multi={multi}\n{traceback.format_exc()}",
                          flush=True)
    print(f"[dryrun] done: {n_ok} ok, {n_skip} skipped, {n_fail} failed")
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
