"""The grid survivor gather and central accept in lane blocks
(``rounds.gather_accept``) on four virtual CPU devices: blocks of
ceil(J/m) lanes give the answers of one gather of the whole stack bit for
bit, the answer keeps Theorem 8's bound against sequential greedy and its
reported value matches a float64 recomputation, and the gather counters
read what was built.  Four virtual devices exist only in a fresh process,
so each case runs in one."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: builds the case's programs and prints one JSON line of what they gave
CASE = r'''
import json, math, sys
import numpy as np
import jax, jax.numpy as jnp
from repro.core import functions as F, mapreduce as mr, rounds, sequential
from repro.launch.mesh import make_mesh_for

case = sys.argv[1]
N, D, K, EPS, GROUPS = 4096, 128, 16, 0.15, 16
chips = 1 if case == "one_device" else 4
mesh = make_mesh_for(chips, model_parallel=1)
oracle = F.FeatureCoverage(feat_dim=D, use_kernel=True)
cfg = mr.MRConfig(k=K, n_total=N, n_machines=chips, eps=EPS,
                  engine="fused", chunk=128)
J = cfg.grid_size()

# grouped rows: GROUPS runs of contiguous rows, each lit on its own band
rng = np.random.default_rng(7)
grp = np.arange(N) * GROUPS // N
band = np.arange(D) * GROUPS // D
bright = rng.uniform(0.2, 0.66, GROUPS)[grp][:, None]
X = np.where(band[None, :] == grp[:, None],
             bright * rng.uniform(0.75, 1.25, (N, D)), 0.0).astype(np.float32)
Xd = jnp.asarray(X)
ids = jnp.arange(N, dtype=jnp.int32)
key = jax.random.PRNGKey(3)

def stats():
    s = rounds.gather_stats()
    return [s["lane_blocks"], s["block_lanes"], s["bytes_in"]]

def answer(res):
    return {"ids": np.asarray(res.sol_ids).tolist(),
            "size": np.asarray(res.sol_size).tolist(),
            "value": np.asarray(res.value).tolist()}

out = {"J": J}
if case == "batch":
    qb = mr.make_query_batch([K, K // 2])
    for name, block in (("blocked", None), ("whole", 2 * J)):
        run, _ = mr.two_round_batch_mesh(oracle, cfg, mesh,
                                         _block_lanes=block)
        out[name] = answer(jax.jit(run)(Xd, ids, qb, key))
        out[name + "_stats"] = stats()
else:
    for name, block in (("blocked", None), ("whole", J)):
        run, _ = mr.two_round_mesh(oracle, cfg, mesh, _block_lanes=block)
        out[name] = answer(jax.jit(run)(Xd, ids, key))
        out[name + "_stats"] = stats()
    _, _, greedy_value = sequential.greedy(oracle, Xd, jnp.ones(N, bool), K)
    out["greedy"] = float(greedy_value)
    S = [i for i in out["blocked"]["ids"] if i >= 0]
    out["f64"] = float(np.sqrt(X[S].astype(np.float64).sum(0)).sum())
print(json.dumps(out))
'''


def run_case(case: str) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(ROOT, "src"))
    p = subprocess.run([sys.executable, "-c", CASE, case], env=env,
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def blocks_of(lanes: int, m: int):
    block = -(-lanes // m)
    return -(-lanes // block), block


@pytest.mark.parametrize("case", ["four_machines", "batch", "one_device"])
def test_lane_blocks_answer_as_one_gather_of_the_whole_stack(case):
    out = run_case(case)
    J = out["J"]
    if case == "one_device":
        # one machine: one block of every lane, nothing received
        assert out["blocked_stats"] == [1, J, 0]
        assert out["blocked"] == out["whole"]
        return
    lanes = 2 * J if case == "batch" else J
    assert out["blocked_stats"][:2] == list(blocks_of(lanes, 4))
    assert out["whole_stats"][:2] == [1, lanes]
    # the equal blocks overlap by what ceil leaves over: those lanes are
    # gathered twice, and every lane of the stack at least once
    n_blocks, block = blocks_of(lanes, 4)
    extra = (n_blocks * block - lanes) / lanes
    survivors_in = out["whole_stats"][2]
    assert survivors_in < out["blocked_stats"][2] <= (1 + extra) * \
        survivors_in
    assert out["blocked"] == out["whole"]          # bit for bit
    if case == "four_machines":
        eps = 0.15
        assert out["blocked"]["value"] >= (0.5 - eps) * out["greedy"]
        assert abs(out["blocked"]["value"] - out["f64"]) <= \
            2e-6 * out["f64"]
