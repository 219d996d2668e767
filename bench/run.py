"""Run one cell of the benchmark on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s>
        --trace <0|1>

The cell, its configuration, traffic mix and metrics are found by name
(``BENCHMARK.json`` and the files under ``bench/``).  With no TPU, or fewer
chips than the cell asks for, it exits non-zero and prints no result.  The
last line of standard output is the result: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (with ``--trace 1`` also
``breakdown``) and ``checks``, the numbers compared beside their limits.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # the TPU runtime's own logs go inside the checkout, not to /tmp
    os.environ.setdefault("TPU_LOG_DIR",
                          os.path.join(ROOT, ".bench_out", "tpu_logs"))
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    try:
        import repro  # noqa: F401  (the system under test)
        from bench import harness
    except ImportError as e:
        print(f"[bench] the system under test is not here: {e}",
              file=sys.stderr)
        return 2
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        benchmark = json.load(f)
    try:
        out = harness.run(args.workload, args.seed, args.seconds,
                          bool(args.trace), t_start=T_START,
                          benchmark=benchmark)
    except harness.NoDevice as e:
        print(f"[bench] {e}", file=sys.stderr)
        return 3
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
