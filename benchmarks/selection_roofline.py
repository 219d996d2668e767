"""Roofline the paper's technique itself on the production mesh (§Perf
pair 3): lower + compile `two_round_mesh` (Theorem 8, the production
selection step) for a pod-scale instance and derive the three roofline
terms, baseline vs the TPOracle optimization (feature dim sharded over the
idle "model" axis during the replicated central phase).

Standalone (needs 512 host devices):
    PYTHONPATH=src python -m benchmarks.selection_roofline
Inside benchmarks.run it only *reports* previously saved records (the
512-device XLA flag cannot be set after jax is initialized).
"""

import json
import os

RESULTS = os.path.join(os.path.dirname(__file__), "..", "results",
                       "dryrun")

# pod-scale instance: 4M documents, 256-dim embeddings, select 4096
N, D, K = 1 << 22, 256, 4096


def measure() -> list:
    import jax
    import jax.numpy as jnp
    from repro.core.selector import DistributedSelector, SelectorSpec
    from repro.launch.mesh import make_production_mesh
    from repro.roofline import analysis as RL

    mesh = make_production_mesh()
    rows = []
    for tag, tp, prec in (("baseline", False, "f32"),
                          ("tp_oracle", True, "f32"),
                          ("bf16_storage", False, "bf16")):
        spec = SelectorSpec(k=K, oracle="feature_coverage",
                            algorithm="two_round", oracle_tp=tp,
                            precision=prec)
        sel = DistributedSelector(spec, mesh, n_total=N, feat_dim=D)
        # the corpus arrives at the policy's storage dtype — the HLO the
        # roofline reads then carries 2-byte feature planes under bf16
        # instead of a hardwired f32 assumption
        feats = jax.ShapeDtypeStruct((N, D),
                                     spec.precision_policy.storage)
        ids = jax.ShapeDtypeStruct((N,), jnp.int32)
        key = jax.ShapeDtypeStruct((2,), jnp.uint32)
        with mesh:
            jitted = jax.jit(sel._run)
            lowered = jitted.lower(feats, ids, key)
            compiled = lowered.compile()
        cost = compiled.cost_analysis() or {}
        coll = RL.collective_bytes(compiled.as_text())
        mem = compiled.memory_analysis()
        rl = RL.from_costs(f"selection/two_round/{tag}", mesh.size, cost,
                           coll,
                           peak_memory_bytes=float(
                               getattr(mem, "temp_size_in_bytes", 0)),
                           device_kind=RL.V5E)
        rec = {"arch": "selection-two-round", "shape": f"n{N}_k{K}_d{D}",
               "mesh": "pod16x16", "tag": tag, "precision": prec,
               "chips": mesh.size,
               "skipped": False, "seconds_lower": 0.0,
               "seconds_compile": 0.0,
               "memory_analysis": {"temp_gb": float(
                   getattr(mem, "temp_size_in_bytes", 0)) / 2**30},
               "cost_analysis": {k: v for k, v in cost.items()
                                 if isinstance(v, (int, float))},
               "roofline": rl.row(), "hlo_bytes": 0, "n_collectives": -1}
        os.makedirs(RESULTS, exist_ok=True)
        with open(os.path.join(
                RESULTS, f"selection__n{N}_k{K}__pod16x16__{tag}.json"),
                "w") as f:
            json.dump(rec, f, indent=1, default=float)
        r = rl.row()
        print(f"[selection-roofline] {tag:10s} "
              f"compute={r['t_compute_s']:.3f}s "
              f"memory={r['t_memory_s']:.3f}s "
              f"collective={r['t_collective_s']:.3f}s "
              f"bottleneck={r['bottleneck']}", flush=True)
        rows.append(rec)
    return rows


def run(quick: bool = False) -> list:
    """Report mode (safe inside benchmarks.run)."""
    import glob
    from benchmarks.common import print_table, save
    rows = []
    for path in sorted(glob.glob(os.path.join(
            RESULTS, "selection__*.json"))):
        with open(path) as f:
            rec = json.load(f)
        r = rec["roofline"]
        rows.append({"tag": rec["tag"],
                     "t_compute_s": r["t_compute_s"],
                     "t_memory_s": r["t_memory_s"],
                     "t_collective_s": r["t_collective_s"],
                     "bottleneck": r["bottleneck"],
                     "temp_gb": rec["memory_analysis"]["temp_gb"]})
    print_table("selection_roofline (paper technique on the pod)", rows)
    save("selection_roofline", rows)
    return rows


if __name__ == "__main__":
    import os as _os
    _os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    measure()
