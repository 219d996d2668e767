"""Readings for the limits of ``bench/check.py``: the program over many
seeds, and the controls and planted faults of ``bench/faults.py``, each
over several seeds, in one process (set-up compiles once).

    python3 bench/control.py --workload <cell> --mode <mode> \
        --seeds 1,2,3 [--seconds 5]

``--mode none`` is the program as the configuration states it.  Prints one
line per seed, ``reading <cell> <mode> <seed> correct=<bool> <number>=<value>
...``, and nothing else on standard output.  A benchmark run never runs
this.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--mode", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import faults, harness
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        benchmark = json.load(f)
    cell = harness.load_cell(args.workload)
    if args.mode not in ("none",) + faults.CONTROLS + faults.FAULTS:
        ap.error(f"--mode must be none, one of {faults.CONTROLS} or one "
                 f"of {faults.FAULTS}")
    if args.mode != "none" and not faults.applies(args.mode, cell):
        print(f"[bench] {args.mode} does not apply to {args.workload}",
              file=sys.stderr)
        return 2
    for seed in (int(s) for s in args.seeds.split(",")):
        gc.collect()
        t0 = time.monotonic()
        try:
            with faults.planted(args.mode):
                out = harness.run(
                    args.workload, seed, args.seconds, False, t_start=t0,
                    benchmark=benchmark,
                    config_overrides=faults.config_overrides(args.mode),
                    patch=faults.after_setup(args.mode))
        except Exception as e:  # noqa: BLE001 - a control may crash
            print(f"reading {args.workload} {args.mode} {seed} "
                  f"correct=False crashed={type(e).__name__}:{e}"[:2000],
                  flush=True)
            continue
        nums = " ".join(f"{k}={v['value']!r}"
                        for k, v in out["checks"].items())
        print(f"reading {args.workload} {args.mode} {seed} "
              f"correct={out['correct']} {nums} "
              f"attempted={out['attempted']}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
