"""Benchmark: aggregate results/dryrun/*.json into the §Roofline table.

Reads every dry-run record (written by repro.launch.dryrun), prints the
three-term roofline per (arch x shape x mesh), the dominant bottleneck,
MODEL_FLOPS / HLO_FLOPs, and the per-device memory — i.e. the §Roofline
section of EXPERIMENTS.md regenerates from this module.
"""

from __future__ import annotations

import glob
import json
import os

from benchmarks.common import print_table, save

DRYRUN_DIR = os.path.join(os.path.dirname(__file__), "..", "results",
                          "dryrun")


def rows_from_dir(dryrun_dir: str = DRYRUN_DIR, mesh: str = None,
                  include_tagged: bool = False) -> list:
    rows = []
    for path in sorted(glob.glob(os.path.join(dryrun_dir, "*.json"))):
        with open(path) as f:
            rec = json.load(f)
        if rec.get("skipped"):
            continue
        if rec.get("tag") and not include_tagged:
            continue
        if mesh and rec.get("mesh") != mesh:
            continue
        r = rec["roofline"]
        # recompute model-flops-derived metrics from the current config
        # definitions (records store raw costs; definitions can improve)
        mf = _model_flops(rec["arch"], rec["shape"])
        hlo_total = r["hlo_flops_per_dev"] * rec["chips"]
        t_bound = max(r["t_compute_s"], r["t_memory_s"], r["t_collective_s"])
        from repro.roofline.analysis import peaks_for
        rows.append({
            "arch": rec["arch"], "shape": rec["shape"], "mesh": rec["mesh"],
            "tag": rec.get("tag", ""),
            "t_compute_s": r["t_compute_s"],
            "t_memory_s": r["t_memory_s"],
            "t_collective_s": r["t_collective_s"],
            "bottleneck": r["bottleneck"],
            "useful_flops_frac": mf / hlo_total if hlo_total else 0.0,
            "mfu_bound": (mf / rec["chips"] / t_bound)
            / peaks_for(r["device_kind"]).flops
            if t_bound else 0.0,
            "mem_gb_per_dev": r["peak_memory_gb"],
            "compile_s": rec["seconds_compile"],
        })
    return rows


def _model_flops(arch: str, shape_name: str) -> float:
    from repro.configs.base import SHAPES, get_config
    from repro.roofline.analysis import model_flops_for
    try:
        return model_flops_for(get_config(arch), SHAPES[shape_name])
    except Exception:
        return 0.0


def run(quick: bool = False) -> list:
    rows = [r for r in rows_from_dir(include_tagged=True)
            if r["tag"] in ("", "opt")]
    print_table("roofline (from dry-run artifacts; tag 'opt' = optimized "
                "parallelism per §Perf)", rows)
    save("roofline_report", rows)
    n_multi = sum(1 for r in rows if r["mesh"] == "pod2x16x16")
    n_single = sum(1 for r in rows if r["mesh"] == "pod16x16")
    n_opt = sum(1 for r in rows if r["tag"] == "opt")
    print(f"cells: {n_single} single-pod + {n_multi} multi-pod "
          f"({n_opt} optimized)")
    return rows


if __name__ == "__main__":
    run()
