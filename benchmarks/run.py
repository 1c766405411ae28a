"""The benchmark's one command.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, one cell. It fails, printing no result, unless JAX's backend is
a TPU whose kind is in ``benchmarks/peaks.json`` with as many chips as the
cell asks for. ``--rehearsal`` is the one way off the chip: it runs the same
code end to end at a tiny size under ``JAX_PLATFORMS=cpu`` (sizes from
``benchmarks/rehearsal.json``), prints counts and ``correct`` and no metric.

What belongs to one cell is data: ``BENCHMARK.json`` names the cell's
configuration (``benchmarks/configs/``) and traffic mix (``benchmarks/traffic/``);
the mix names its driver (``benchmarks/drivers/``); ``benchmarks/limits/`` holds
the limits of the numbers compared; each per-layer metric has a file under
``benchmarks/metrics/`` that names its reader under ``benchmarks/readers/``.
"""

from __future__ import annotations

import time

PROCESS_START = time.monotonic()

import argparse
import importlib
import json
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny sizes on the CPU; prints no metric")
    ap.add_argument("--control", default=None,
                    help="put a control in the program's place (must come out "
                         "not correct): int8 for serving; fp8 or half_batch for "
                         "training. Never used by a measured run.")
    ap.add_argument("--benchmark-json", default=str(ROOT / "BENCHMARK.json"),
                    help="the cells and metrics to run from (the tests try a "
                         "cell that BENCHMARK.json does not hold yet)")
    ap.add_argument("--traffic-dir", default=str(ROOT / "benchmarks" / "traffic"),
                    help="where the cell's traffic mix is found (the same tests)")
    ap.add_argument("--dump", default=None,
                    help="a directory for the reduced trace and the run's facts "
                         "(small JSON), for whoever reads the run afterwards")
    return ap.parse_args(argv)


def _merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = _merge(out[k], v) if isinstance(v, dict) and isinstance(out.get(k), dict) else v
    return out


def main(argv=None) -> int:
    args = parse(argv)
    bench = json.loads(pathlib.Path(args.benchmark_json).read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        raise SystemExit(f"no workload {args.workload!r} in {args.benchmark_json}: {sorted(cells)}")
    workload = cells[args.workload]

    from benchmarks import costs, harness, traffic

    limit = harness.fsize_limit()
    print(f"RLIMIT_FSIZE: {'unlimited' if limit is None else limit} bytes; "
          f"this benchmark writes no file over {harness.MAX_FILE_BYTES}", file=sys.stderr)
    if args.rehearsal and os.environ.get("JAX_PLATFORMS") != "cpu":
        raise SystemExit("--rehearsal runs only under JAX_PLATFORMS=cpu")

    mix = traffic.load(workload["traffic"], args.traffic_dir)
    import jax

    devices = jax.local_devices()
    if not args.rehearsal:
        if jax.default_backend() != "tpu":
            print(f"backend is {jax.default_backend()!r}, not tpu: no result", file=sys.stderr)
            return 3
        if len(devices) < workload["chips"]:
            print(f"{len(devices)} chips, the cell asks for {workload['chips']}: no result",
                  file=sys.stderr)
            return 3
    from dalle_pytorch_tpu.compile_cache import enable_compile_cache

    # jax's own thresholds stay: a program that compiles in under a second
    # is compiled again in every run's set-up and takes no room in the cache
    cache_dir = enable_compile_cache()

    cfg = costs.load_config(workload["config"])
    if args.rehearsal:
        tiny = json.loads((ROOT / "benchmarks" / "rehearsal.json").read_text())
        cfg = _merge(cfg, tiny["config"])
        mix = _merge(mix, tiny["traffic"].get(mix["kind"], {}))
        limits = {"limits": tiny["limits"]}
    else:
        limits = json.loads(
            (ROOT / "benchmarks" / "limits" / f"{workload['name']}.json").read_text()
        )
    ctx = harness.Context(
        workload=workload, cfg=cfg, mix=mix, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), rehearsal=args.rehearsal, control=args.control,
        process_start=PROCESS_START, chips=workload["chips"],
        device_kind=devices[0].device_kind,
    )
    if not args.rehearsal:
        ctx.peaks = costs.load_peaks(ctx.device_kind)
    ctx.facts["limits"] = limits["limits"]
    ctx.facts["compile_counter"] = harness.CompileCounter()
    print(f"cell {workload['name']} seed {args.seed} on {ctx.chips} x {ctx.device_kind}; "
          f"compile cache at {cache_dir}", file=sys.stderr)

    driver = importlib.import_module(f"benchmarks.drivers.{mix['driver']}")
    driver.run(ctx)
    total = time.monotonic() - PROCESS_START
    print(f"seconds: set-up {ctx.facts['setup_s']:.1f}, window {ctx.facts.get('window_s', 0.0):.1f}, "
          f"whole run {total:.1f}; backend compiles in set-up "
          f"{ctx.facts.get('compiles_in_setup', 'not counted')}", file=sys.stderr)

    if not args.rehearsal:
        from dalle_pytorch_tpu.ops import kv_policy

        interpreted = [r for r in kv_policy.ROUTE_LOG if r.get("interpret")]
        if interpreted:
            raise SystemExit(f"a kernel ran interpreted on the chip: {interpreted}")
    if ctx.compiles_in_window:
        ctx.compare("compiles_in_window", ctx.compiles_in_window, 0)
    ctx.end_to_end["setup_s"] = ctx.facts["setup_s"]

    if args.rehearsal or args.control:
        # neither is a measurement: counts and ``correct`` only
        metrics = {}
        print(f"no metric printed: end-to-end names {sorted(ctx.end_to_end)}, per-layer names "
              f"{sorted(harness.read_per_layer(ctx, bench)) if not ctx.control else []}",
              file=sys.stderr)
    elif args.trace:
        metrics = harness.read_per_layer(ctx, bench)
    else:
        metrics = {}
        for entry in bench["end_to_end"]:
            cells_of = entry.get("workloads")
            if cells_of is not None and workload["name"] not in cells_of:
                continue
            metrics[entry["name"]] = {
                "value": float(ctx.end_to_end[entry["name"]]), "unit": entry["unit"],
            }
    if args.dump:
        harness.dump(ctx, args.dump)
    harness.emit(ctx, metrics, devices)
    return 0


if __name__ == "__main__":
    sys.exit(main())
