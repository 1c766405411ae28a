"""Several seeds of one cell in ONE process, on the chip, by hand:

    python3 benchmarks/tests/many_seeds.py --workload <cell> --seeds 1,2,3 \
        [--controls none,fp8,half_batch] [--seconds 2] [--benchmark-json F]

Each seed is a whole run of ``benchmarks/run.py`` (set-up, a short window, the
reference, the comparison) and prints its own result line; the process, the
chip and the compile cache are shared, which is what makes a dozen seeds and
the controls affordable where set-up is long. ``none`` is the program itself.
Not a measurement: ``setup_s`` of every run but the first is meaningless.
"""

import argparse
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from benchmarks import run as bench_run  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", default="none")
    ap.add_argument("--seconds", default="2")
    ap.add_argument("--benchmark-json", default=None)
    ap.add_argument("--rehearsal", action="store_true", help="on the CPU, to try this script")
    args = ap.parse_args()
    worst = 0
    for control in args.controls.split(","):
        for seed in args.seeds.split(","):
            argv = ["--workload", args.workload, "--seed", seed,
                    "--seconds", args.seconds, "--trace", "0"]
            if control != "none":
                argv += ["--control", control]
            if args.rehearsal:
                argv += ["--rehearsal"]
            if args.benchmark_json:
                argv += ["--benchmark-json", args.benchmark_json]
            print(f"--- {args.workload} seed {seed} control {control}", flush=True)
            worst = max(worst, bench_run.main(argv))
    return worst


if __name__ == "__main__":
    sys.exit(main())
