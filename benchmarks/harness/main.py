"""The benchmark's command line: one run of one cell, one result line.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Which metrics a cell reports, and their units, come from ``BENCHMARK.json``
at the checkout's root; each metric is read by ``metrics/<name>.py``.
A run that finds no TPU, or fewer chips than the cell asks for, exits 2
and prints no result.
"""

import argparse
import json
import os
import sys

from harness import cell as cells
from harness.peaks import peaks_for

ROOT = os.path.dirname(cells.BENCH_DIR)


def load_benchmark():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def load_workload(name, bench=None):
    """The cell as ``BENCHMARK.json`` names it, with its traffic mix's
    file (``traffic/<traffic>.json``: delivery path, settings)."""
    bench = bench or load_benchmark()
    cell = dict(next(w for w in bench["workloads"] if w["name"] == name))
    with open(os.path.join(cells.BENCH_DIR, "traffic",
                           f"{cell['traffic']}.json")) as f:
        cell.update(json.load(f))
    return cell


def metrics_for(bench, workload, trace):
    """The cell's metrics: end-to-end ones untraced, per-layer ones traced;
    a metric with a ``workloads`` list belongs to those cells only."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if workload in m.get("workloads", [workload])]


def read_metrics(run, metrics):
    out = {}
    for m in metrics:
        reader = cells.load_module(
            os.path.join(cells.BENCH_DIR, "metrics", f"{m['name']}.py"),
            "bench_metric_" + m["name"].replace(".", "_"))
        value = reader.read(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def log(msg):
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--control", type=int, choices=(0, 1), default=0,
                   help="also read the control (the reference in float8) "
                        "and print its numbers; never part of a benchmark run")
    args = p.parse_args(argv)

    bench = load_benchmark()
    cell = load_workload(args.workload, bench)

    # The compile cache lives in this checkout, whatever the machine sets:
    # JAX reads the variable at import; every program is kept, however
    # fast it compiled, so a second run of a cell compiles nothing.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
        ROOT, ".jax_compile_cache")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        log(f"needs {cell['chips']} TPU chip(s); JAX has {len(devices)} "
            f"{devices[0].platform} device(s) ({devices[0].device_kind})")
        return 2
    peaks = peaks_for(devices[0].device_kind)

    from petastorm_tpu.jax_utils.compile_cache import use_compile_cache

    use_compile_cache()
    run, checks = cells.run_cell(cell, args.seed, args.seconds, args.trace,
                                 devices, control=bool(args.control), log=log)
    run.peaks = peaks
    return report(run, checks, bench, args, devices)


def report(run, checks, bench, args, devices):
    program = [(n, v, lim) for n, v, lim in checks
               if not n.startswith(("control.", "fault."))]
    correct = run.failed == 0 and all(v <= lim for _, v, lim in program)
    result = {
        "correct": correct,
        "attempted": run.attempted,
        "failed": run.failed,
        "metrics": read_metrics(run, metrics_for(bench, args.workload,
                                                 args.trace)),
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind,
                   "count": len(devices),
                   "memory_peak_bytes": run.memory_peak_bytes},
    }
    if args.trace:
        result["device"]["busy_s"] = run.trace_summary["busy_s"]
        result["device"]["window_s"] = run.trace_summary["window_s"]
        result["breakdown"] = run.breakdown
    if args.control:
        control = [(n, v, lim) for n, v, lim in checks
                   if n.startswith("control.")]
        result["control_correct"] = all(v <= lim for _, v, lim in control)
    result["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in checks}
    for n, v, lim in checks:
        log(f"check {n} {v!r} limit {lim!r}")
    print(json.dumps(result), flush=True)
    return 0
