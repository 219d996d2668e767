"""The benchmark's harness on the CPU: its files, a rehearsal of each cell at
a tiny size through the harness's own functions (kernels in interpret
mode), a cell found in another directory, the planted faults and controls
coming out as not correct, and the measurement path refusing to run with no
TPU.  No number here is a device number."""

import json
import os
import re
import shutil
import subprocess
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import faults, harness  # noqa: E402

BENCH = os.path.join(ROOT, "bench")
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BJ = json.load(_f)
ONE_CHIP = [w["name"] for w in BJ["workloads"] if w["chips"] == 1]
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device",
               "checks"}
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
#: a size at which the interpreted kernels run in seconds; k = 64 keeps
#: the serving mix's budgets (1-64) within the slot buffers
TINY = {"n": 512, "d": 128, "k": 64, "reference_size": 64}


@pytest.fixture(autouse=True)
def _no_compile_cache(monkeypatch):
    # the CPU rehearsal leaves the checkout's compile cache to the chip
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: "off")


def run_tiny(cell, seed=2 ** 33 + 7, seconds=0.3, **kw):
    return harness.run(cell, seed, seconds, False, t_start=time.monotonic(),
                       benchmark=BJ, require_tpu=False,
                       config_overrides=dict(TINY, **kw.pop("over", {})),
                       **kw)


def test_benchmark_names_units_and_metrics_follow_the_contract():
    assert set(BJ) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert BJ["paths"] == ["bench"] and BJ["command"][1] == "bench/run.py"
    e2e = {m["name"]: m for m in BJ["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in BJ["end_to_end"] + BJ["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert os.path.isfile(os.path.join(BENCH, "metrics",
                                           f"{m['name']}.py")), m["name"]
    for m in BJ["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    cells = {w["name"] for w in BJ["workloads"]}
    for m in BJ["per_layer"]:
        assert m["moves"] in e2e
        for c in m.get("workloads", cells):
            assert c in e2e[m["moves"]].get("workloads", cells)
    for c in cells:
        reported = {m["name"] for m in harness.cell_metrics(BJ, c, False)}
        assert "setup_s" in reported and len(reported) >= 2
        assert harness.cell_metrics(BJ, c, True)


def test_every_cell_and_config_file_parses_and_agrees():
    configs = {c["name"]: c for c in BJ["configs"]}
    pairs = set()
    for w in BJ["workloads"]:
        cell = harness.load_cell(w["name"])
        assert (cell["config"], cell["traffic"], cell["chips"]) == (
            w["config"], w["traffic"], w["chips"])
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        cfg = cell["config_spec"]
        assert cfg["name"] == w["config"] and cfg["machines"] == w["chips"]
        assert os.path.isfile(os.path.join(
            BENCH, "traffic", f"{cell['traffic_spec']['driver']}.py"))
    for name, c in configs.items():
        cfg = harness.load_json(os.path.join(ROOT, c["file"]))
        assert cfg["source"] == c["source"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert set(cfg["limits"]) == {"bad_sets", "missing", "value_gap",
                                      "ratio_min", "shortfall"}


@pytest.mark.parametrize("cell", ONE_CHIP)
def test_cell_rehearsal_yields_the_contract_line(cell):
    out = run_tiny(cell)
    assert set(out) == RESULT_KEYS
    assert list(out)[-1] == "checks"
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    want = {m["name"] for m in harness.cell_metrics(BJ, cell, False)}
    # the CPU backend keeps no memory peak, so that reader finds nothing
    assert set(out["metrics"]) == want - {"peak_hbm_gib"}
    json.dumps(out)


def _cell_dir(tmp_path, config: dict, traffic: str, chips: int) -> str:
    """A cell ``new_cell`` on ``config`` in a directory of its own."""
    for sub in ("cells", "configs", "traffic"):
        (tmp_path / sub).mkdir()
    (tmp_path / "configs" / f"{config['name']}.json").write_text(
        json.dumps(config))
    shutil.copy(os.path.join(BENCH, "traffic", f"{traffic}.json"),
                tmp_path / "traffic" / f"{traffic}.json")
    (tmp_path / "cells" / "new_cell.json").write_text(json.dumps(
        {"config": config["name"], "traffic": traffic, "chips": chips}))
    return str(tmp_path)


def test_four_chip_cell_rehearsal_and_no_exchange_fault(tmp_path):
    # one machine's rows hold a quarter of the groups, so an answer made
    # without the exchange misses three quarters of them
    cfg = dict(harness.load_cell("coverage_two_round")["config_spec"],
               name="four_machines", machines=4)
    root = _cell_dir(tmp_path, cfg, "batch_closed", 4)
    # four virtual CPU devices exist only in a fresh process
    code = (
        "import json, sys, time; sys.path[:0] = [%r, %r]\n"
        "from bench import faults, harness\n"
        "harness.enable_compile_cache = lambda: 'off'\n"
        "bj = json.load(open(%r))\n"
        "for mode in ('none', 'no_exchange'):\n"
        "    with faults.planted(mode):\n"
        "        out = harness.run('new_cell', 5, 0.3, False,"
        " t_start=time.monotonic(), benchmark=bj, root=%r,"
        " require_tpu=False, config_overrides=%r)\n"
        "    print(json.dumps(out))\n" % (
            ROOT, os.path.join(ROOT, "src"),
            os.path.join(ROOT, "BENCHMARK.json"), root,
            dict(TINY, n=1024)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    sound, fault = [json.loads(line)
                    for line in p.stdout.strip().splitlines()[-2:]]
    assert sound["correct"] is True, sound["checks"]
    assert sound["device"]["count"] == 4
    assert fault["correct"] is False, fault["checks"]


def test_exemplar_config_rehearsal_yields_the_contract_line(tmp_path):
    cfg = dict(harness.load_cell("coverage_two_round")["config_spec"],
               name="exemplar", oracle="exemplar",
               kernel_ops={"marginals": ["exemplar_marginals"],
                           "accept": ["exemplar_accept"]})
    root = _cell_dir(tmp_path, cfg, "batch_closed", 1)
    out = harness.run("new_cell", 11, 0.3, False, t_start=time.monotonic(),
                      benchmark=BJ, root=root, require_tpu=False,
                      config_overrides=TINY)
    assert set(out) == RESULT_KEYS and out["attempted"] > 0
    assert out["checks"]["bad_sets"]["value"] == 0
    assert out["checks"]["value_gap"]["value"] < 1e-5, out["checks"]


def test_a_cell_file_in_another_directory_runs_unedited(tmp_path):
    for sub in ("cells", "configs", "traffic"):
        (tmp_path / sub).mkdir()
    shutil.copy(os.path.join(BENCH, "configs", "tinyimg_coverage.json"),
                tmp_path / "configs" / "tinyimg_coverage.json")
    (tmp_path / "traffic" / "two_rates.json").write_text(json.dumps(
        {"driver": "open_loop", "rate_per_s": 20.0, "budget_min": 2,
         "budget_max": 9, "deadline_ms": None}))
    (tmp_path / "cells" / "new_cell.json").write_text(json.dumps(
        {"config": "tinyimg_coverage", "traffic": "two_rates", "chips": 1}))
    bj = dict(BJ, end_to_end=BJ["end_to_end"] + [])
    out = harness.run("new_cell", 3, 0.3, False, t_start=time.monotonic(),
                      benchmark=bj, root=str(tmp_path), require_tpu=False,
                      config_overrides=dict(TINY, slots=4))
    assert out["correct"] is True
    assert out["attempted"] == 6     # 20 requests/s over 0.3 s


def _run_py(cwd, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(extra_env or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "coverage_two_round",
         "--seed", str(2 ** 40 + 1), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_measurement_path_exits_nonzero_without_a_tpu():
    p = _run_py(ROOT)
    assert p.returncode != 0
    assert "no TPU found" in p.stderr
    assert "{" not in p.stdout


def test_benchmark_alone_exits_nonzero(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_py(str(tmp_path))
    assert p.returncode != 0
    assert "{" not in p.stdout


CASES = [("coverage_two_round", "alter_answer"),
         ("coverage_two_round", "stale_state"),
         ("coverage_two_round", "bf16"),
         ("coverage_two_round", "half_corpus"),
         ("coverage_two_round", "half_features"),
         ("coverage_serve", "alter_answer"),
         ("coverage_serve", "stale_state"),
         ("coverage_serve", "bf16"),
         ("coverage_serve", "half_slots"),
         ("coverage_serve", "half_features")]


@pytest.mark.parametrize("cell,mode", CASES)
def test_planted_fault_or_control_is_not_correct(cell, mode):
    assert faults.applies(mode, harness.load_cell(cell))
    with faults.planted(mode):
        out = run_tiny(cell, over=faults.config_overrides(mode),
                       patch=faults.after_setup(mode))
    assert out["correct"] is False, out["checks"]
