"""Entry-point plumbing: where the compile cache lives, and the table of
device peaks the roofline divides by."""

import os
import subprocess
import sys

import jax
import pytest

from repro.launch import compile_cache
from repro.roofline import analysis as RL

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.enable_compile_cache()
        assert path == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_env_dir_is_the_only_one_written(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set the entry point sets nothing in
    code: a run of repro.launch.select fills that directory, and the
    checkout's own cache directory gains nothing."""
    checkout_cache = os.path.join(REPO, ".jax_cache")
    before = set(os.listdir(checkout_cache)) \
        if os.path.isdir(checkout_cache) else set()
    env = dict(os.environ,
               JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(REPO, "src"))
    subprocess.run([sys.executable, "-m", "repro.launch.select", "--n",
                    "256", "--k", "4", "--d", "8"], env=env, cwd=REPO,
                   check=True, capture_output=True, timeout=300)
    assert os.listdir(tmp_path)
    after = set(os.listdir(checkout_cache)) \
        if os.path.isdir(checkout_cache) else set()
    assert after == before


def test_peaks_are_keyed_by_device_kind():
    v5e = RL.peaks_for(RL.V5E)
    assert (v5e.flops, v5e.hbm_bw) == (197e12, 819e9)
    rl = RL.from_costs("x", 1, {"flops": 197e12, "bytes accessed": 0.0}, {},
                       device_kind=RL.V5E)
    assert rl.t_compute == pytest.approx(1.0)
    with pytest.raises(ValueError, match="no published peaks"):
        RL.peaks_for("cpu")
    with pytest.raises(ValueError, match="no published peaks"):
        RL.from_costs("x", 1, {}, {}, device_kind="TPU v4")
