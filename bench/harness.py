"""The benchmark's harness: finds a cell's files by name, runs its traffic
driver, judges the answers and reduces the numbers.

Everything that belongs to one cell, configuration, traffic mix or metric
is a file of its own, found by its name:

* ``cells/<cell>.json``: the configuration, the traffic mix and the chips;
* ``configs/<config>.json``: one selection deployment, with its limits;
* ``traffic/<mix>.json``: one traffic mix, naming its ``driver``;
* ``traffic/<driver>.py``: the general generators (``batch_loop``,
  ``open_loop``), each with ``setup`` (which leaves the corpus ``X``, the
  reference set ``ref`` and ``rows_of`` in the context), ``window`` and
  ``answers``;
* ``metrics/<metric>.py``: one reader per metric, ``read(ctx)``, which
  returns a number or None where it finds nothing to read.
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import shutil
import sys
import time
import types
from typing import Optional

import jax
import numpy as np

from bench import check, trace_reduce
from bench.peaks import peaks_for

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
#: the persistent compile cache, at one fixed path inside the checkout
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
#: what a run leaves behind (traces), inside the checkout
OUT_DIR = os.path.join(ROOT, ".bench_out")
#: host spans the traced run records around the calls into each layer
SPANS = ("window", "select", "readback", "run_step", "submit",
         "wait_arrival")


class NoDevice(RuntimeError):
    """JAX finds no accelerator, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str) -> types.ModuleType:
    """Import a file by path (names may hold dots, as metric names do)."""
    name = "bench_file_" + os.path.relpath(path, BENCH).replace(
        os.sep, "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str, root: str = BENCH) -> dict:
    """The cell ``name`` with its configuration and traffic mix, read from
    ``root``'s ``cells/``, ``configs/`` and ``traffic/``."""
    cell = load_json(os.path.join(root, "cells", f"{name}.json"))
    cell["name"] = name
    cell["config_spec"] = load_json(
        os.path.join(root, "configs", f"{cell['config']}.json"))
    cell["traffic_spec"] = load_json(
        os.path.join(root, "traffic", f"{cell['traffic']}.json"))
    return cell


def load_driver(cell: dict) -> types.ModuleType:
    return load_module(os.path.join(
        BENCH, "traffic", f"{cell['traffic_spec']['driver']}.py"))


def cell_metrics(benchmark: dict, cell: str, trace: bool) -> list:
    """The metrics the cell reports: its end-to-end ones untraced, its
    per-layer ones traced.  A metric without ``workloads`` is in every
    cell."""
    group = benchmark["per_layer" if trace else "end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def devices(chips: int, require_tpu: bool) -> list:
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoDevice(f"no TPU found: JAX sees {len(devs)} "
                       f"{devs[0].platform!r} device(s)")
    if len(devs) < chips:
        raise NoDevice(f"the cell asks for {chips} chips, JAX sees "
                       f"{len(devs)}")
    return devs


def enable_compile_cache() -> str:
    """Keep every compiled program in the persistent cache, so that only a
    cell's first run in a checkout compiles.  ``JAX_COMPILATION_CACHE_DIR``
    wins where it is set."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def seed_key(seed: int):
    """A PRNG key from a whole number of up to 64 bits."""
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"--seed must be in [0, 2**64), got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


class Spans:
    """The benchmark's host spans: ``jax.profiler.TraceAnnotation`` when
    the run is traced, nothing otherwise."""

    def __init__(self, on: bool):
        self.on = on

    def __call__(self, name: str):
        if not self.on:
            return contextlib.nullcontext()
        return jax.profiler.TraceAnnotation(name)


def peak_bytes(devs) -> int:
    """The fullest device's peak: its arrays (``peak_bytes_in_use``) plus
    what its programs reserved for their temporaries
    (``peak_bytes_reserved``, held apart from the arrays on the TPU)."""
    def peak(d):
        st = d.memory_stats() or {}
        return st.get("peak_bytes_in_use", 0) + st.get(
            "peak_bytes_reserved", 0)
    return max(peak(d) for d in devs)


def run(cell_name: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, benchmark: dict, root: str = BENCH,
        require_tpu: bool = True, config_overrides: Optional[dict] = None,
        patch=None) -> dict:
    """One run of a cell; returns the result line's object.

    ``config_overrides`` (tests: a tiny n) and ``patch`` (a callable given
    the traffic driver's context after set-up, which may break the timed
    path for a control or a fault) never appear in a benchmark run."""

    cell = load_cell(cell_name, root)
    cfg = dict(cell["config_spec"], **(config_overrides or {}))
    if cfg.get("machines", 1) != cell["chips"]:
        raise ValueError(f"cell {cell_name}: {cell['chips']} chips for a "
                         f"deployment of {cfg.get('machines', 1)} machines")
    devs = devices(cell["chips"], require_tpu)[:cell["chips"]]
    cache = enable_compile_cache()
    log(f"cell {cell_name}: config {cell['config']}, traffic "
        f"{cell['traffic']}, {cell['chips']} chip(s) of "
        f"{devs[0].device_kind!r}; seed {seed}; compile cache {cache}")
    driver = load_driver(cell)
    spans = Spans(trace)
    ctx = types.SimpleNamespace(
        cell=cell, config=cfg, traffic=cell["traffic_spec"],
        chips=cell["chips"], devices=devs, seed=seed, key=seed_key(seed),
        seconds=seconds, spans=spans, log=log, trace=None)
    driver.setup(ctx)
    if patch is not None:
        patch(ctx)
    jax.effects_barrier()
    t_window = time.monotonic()
    ctx.setup_s = t_window - t_start
    log(f"set-up {ctx.setup_s:.3f} s; window of {seconds} s starts")

    trace_dir = os.path.join(OUT_DIR, "trace", cell_name)
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
    try:
        with spans("window"):
            driver.window(ctx)
    finally:
        if trace:
            jax.profiler.stop_trace()
    ctx.peak_bytes = peak_bytes(devs)
    log(f"device memory after the window: {devs[0].memory_stats()}")

    answers, missing = driver.answers(ctx)
    t_ref = time.monotonic()
    ref_host = None if ctx.ref is None else np.asarray(ctx.ref, np.float64)
    greedy = check.greedy_prefix_f64(cfg["oracle"], ctx.X, cfg["k"],
                                     ctx.rows_of, ref=ctx.ref,
                                     ref_host=ref_host)
    verdict = check.compare(cfg["oracle"], answers, ctx.rows_of, cfg["n"],
                            greedy, cfg["limits"], missing=missing,
                            ref_host=ref_host)
    sizes = sorted(a.size for a in answers) or [0]
    log(f"reference and comparison took {time.monotonic() - t_ref:.3f} s "
        f"over {len(answers)} answers of |S| {sizes[0]} to {sizes[-1]} "
        f"(median {sizes[len(sizes) // 2]})")
    ctx.value_ratio = verdict["value_ratio"]

    if trace:
        ctx.trace = trace_reduce.load_xplane(trace_dir)
        win = trace_reduce.host_spans(ctx.trace, ["window"])
        ctx.window_ns = (win[0][1], win[0][2])
        ctx.peaks = peaks_for(devs[0].device_kind)
    metrics = {}
    for m in cell_metrics(benchmark, cell_name, trace):
        value = load_module(os.path.join(BENCH, "metrics",
                                         f"{m['name']}.py")).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(jax.devices()), "memory_peak_bytes":
              ctx.peak_bytes}
    out = {"correct": verdict["correct"], "attempted": ctx.attempted,
           "failed": verdict["failed"], "metrics": metrics,
           "device": device}
    if trace:
        w = ctx.window_ns
        planes = trace_reduce.device_planes(ctx.trace)
        device["busy_s"] = sum(trace_reduce.busy_ns(p, w)
                               for p in planes) / len(planes) / 1e9
        device["window_s"] = (w[1] - w[0]) / 1e9
        out["breakdown"] = trace_reduce.breakdown(ctx.trace, w, SPANS)
    log(f"run took {time.monotonic() - t_start:.3f} s from the process's "
        f"start")
    check.print_numbers(verdict["numbers"])
    out["checks"] = verdict["numbers"]
    # the context holds the corpus and closures over itself: drop it now,
    # so that a caller's next run finds the device empty
    ctx.__dict__.clear()
    return out
