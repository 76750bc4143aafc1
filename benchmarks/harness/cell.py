"""One run of a training-input cell: set-up, three warm-up steps, the
measured window, an optional profiler trace, and the check.

The cell's workload file names its configuration (``configs/<config>.py``
and ``.json``) and its delivery path (``harness/paths/<path>.py``); this
module knows neither.
"""

import functools
import gc
import importlib.util
import json
import os
import shutil
import time

import numpy as np

from harness import compare, hostcpu

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATA_DIR = os.path.join(BENCH_DIR, ".data")
OUT_DIR = os.path.join(BENCH_DIR, "out")

#: Steps driven through the window's own call and feed before the window:
#: they compile and warm every program, and the reference follows them.
WARM_STEPS = 3
#: A traced run measures (and traces) at most this many seconds.
TRACE_SECONDS = 8
#: The jit name of the consumer step: the trace finds its device time by it.
STEP_NAME = "bench_step"
#: How many datasets stay under ``DATA_DIR``: the newest.
KEEP_DATASETS = 2
#: Written last into a dataset's directory: the sizes it was made at.
DATASET_DONE = ".complete"


def load_module(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_config(name):
    return load_module(os.path.join(BENCH_DIR, "configs", f"{name}.py"),
                       f"bench_config_{name}")


def load_path(name):
    return load_module(os.path.join(BENCH_DIR, "harness", "paths",
                                    f"{name}.py"), f"bench_path_{name}")


def dataset(cfg, config, sz, seed, log):
    """The seed's dataset, in ``DATA_DIR/<config>-<seed>/``. A run of a seed
    whose files are there at the same sizes writes nothing and only
    rebuilds what the reference needs from the seed; otherwise the files
    are written anew, and the oldest datasets go so that at most
    ``KEEP_DATASETS`` stay."""
    path = os.path.join(DATA_DIR, f"{config}-{seed}")
    done = os.path.join(path, DATASET_DONE)
    stamp = json.dumps(sz, sort_keys=True)
    try:
        with open(done) as f:
            cached = f.read() == stamp
    except OSError:
        cached = False
    if not cached:
        shutil.rmtree(path, ignore_errors=True)
        os.makedirs(DATA_DIR, exist_ok=True)
        others = sorted((os.path.join(DATA_DIR, d)
                         for d in os.listdir(DATA_DIR)),
                        key=os.path.getmtime, reverse=True)
        for old in others[KEEP_DATASETS - 1:]:
            shutil.rmtree(old, ignore_errors=True)
    t = time.perf_counter()
    data = cfg.make_dataset(path, sz, seed, write=not cached)
    with open(done, "w") as f:
        f.write(stamp)
    os.utime(path)
    log(f"dataset {data.nbytes} bytes, {'found' if cached else 'written'}, "
        f"in {time.perf_counter() - t:.2f}s")
    return data


class Run:
    """What the metric readers see of one run."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def _named(fn, name):
    def wrapped(*args):
        return fn(*args)

    wrapped.__name__ = wrapped.__qualname__ = name
    return wrapped


def _leaf_norms(tree):
    import jax
    import jax.numpy as jnp

    return jax.tree_util.tree_map(lambda x: jnp.sqrt(jnp.sum(x * x)), tree)


def _change_norms_fn(cfg, sz):
    """Per-leaf norm of the parameters' change since their start, the start
    rebuilt from the seed inside the program (never held beside them)."""
    import jax

    def change(params, key):
        start = cfg.init_params(sz, key)
        return _leaf_norms(jax.tree_util.tree_map(lambda p, s: p - s,
                                                  params, start))

    return jax.jit(change)


def _host(tree):
    import jax

    return {k: float(v) for k, v in
            compare.flat_leaves(jax.device_get(tree)).items()}


def _span(name):
    import jax

    return jax.profiler.TraceAnnotation(name)


def _diag(loader):
    d = loader.diagnostics
    out = {k: v for k, v in d.items() if isinstance(v, (int, float))}
    for k, v in (d.get("source") or {}).items():
        if isinstance(v, (int, float)):
            out[f"source.{k}"] = v
    return out


def run_cell(cell, seed, seconds, trace, devices, overrides=None,
             control=False, log=print):
    """Run ``cell`` once; returns (run, checks) where ``checks`` is a list
    of (name, value, limit)."""
    import jax

    cfg = load_config(cell["config"])
    sz = cfg.load_sizes({**cell.get("settings", {}), **(overrides or {})})
    path = load_path(cell["path"])
    device = devices[0]
    batch = sz["batch_per_chip"]

    data = dataset(cfg, cell["config"], sz, seed, log)

    key = jax.random.PRNGKey(seed)
    t = time.perf_counter()
    params = jax.jit(functools.partial(cfg.init_params, sz),
                     out_shardings=jax.sharding.SingleDeviceSharding(
                         device))(key)
    step = jax.jit(_named(cfg.make_step(sz), STEP_NAME), donate_argnums=(0,))
    change = _change_norms_fn(cfg, sz)
    source = path.open_source(cfg, data, sz, seed)
    try:
        it = iter(source.loader)
        record = cfg.Record(sz, seed, WARM_STEPS)
        prog = {"losses": []}
        for k in range(WARM_STEPS):
            b = next(it)
            record.keep(k, b)
            params, loss = step(params, b)
            prog["losses"].append(float(loss))
            if k == 0:
                prog["change1"] = _host(change(params, key))
        prog["change3"] = _host(change(params, key))
        del b
        log(f"warm-up {time.perf_counter() - t:.2f}s losses {prog['losses']}")
        setup_s = hostcpu.process_age_s()

        window = min(seconds, TRACE_SECONDS) if trace else seconds
        trace_dir = os.path.join(OUT_DIR, f"trace-{cell['name']}-{seed}")
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=options)
        spans = {"loader.next": [], "step.dispatch": [], "step.wait": []}
        completions, losses = [], []
        cpu0, diag0 = hostcpu.cpu_s(source.pids), _diag(source.loader)
        pending, k = None, WARM_STEPS
        t0 = time.perf_counter()
        deadline = t0 + window
        while True:
            a = time.perf_counter()
            with _span("loader.next"):
                b = next(it)
            record.keep(k, b)
            c = time.perf_counter()
            with _span("step.dispatch"):
                params, loss = step(params, b)
            d = time.perf_counter()
            b = None
            if pending is not None:
                with _span("step.wait"):
                    losses.append(float(pending))
                completions.append(time.perf_counter())
                spans["step.wait"].append(completions[-1] - d)
            spans["loader.next"].append(c - a)
            spans["step.dispatch"].append(d - c)
            pending, k = loss, k + 1
            if time.perf_counter() >= deadline:
                break
        with _span("step.wait"):
            losses.append(float(pending))
        completions.append(time.perf_counter())
        t_end = completions[-1]
        cpu1, diag1 = hostcpu.cpu_s(source.pids), _diag(source.loader)
        if trace:
            jax.profiler.stop_trace()
        memory_peak = (device.memory_stats() or {}).get("peak_bytes_in_use",
                                                        0)
        it.close()
    finally:
        source.close()
    del params, loss, pending
    record.to_host()
    gc.collect()

    steps = len(completions)
    run = Run(cell=cell, sizes=sz, seed=seed, chips=cell["chips"],
              setup_s=setup_s, window_s=t_end - t0,
              steps=steps, samples=steps * batch, batch=batch,
              intervals_s=list(np.diff([t0] + completions)),
              cpu_s=cpu1 - cpu0,
              diag={k: diag1[k] - diag0.get(k, 0) for k in diag1},
              spans=spans, memory_peak_bytes=memory_peak,
              attempted=steps,
              failed=int(np.sum(~np.isfinite(losses))),
              trace_summary=None, breakdown=None,
              step_flops=cfg.step_flops(sz, batch),
              input_bytes=cfg.input_bytes(sz, batch))
    if trace:
        from harness import trace as tr

        summary = tr.reduce_dir(trace_dir, STEP_NAME)
        run.trace_summary = summary
        run.breakdown = summary["breakdown"]
        shutil.rmtree(trace_dir, ignore_errors=True)

    checks = check(cfg, data, record, prog, sz, seed, control, log,
                   path.ref_order)
    return run, checks


def reference_training(cfg, data, record, sz, seed, quantize=None,
                       rows=None):
    """The reference's first ``WARM_STEPS`` steps from the seed's start:
    losses, the first gradient's norm per leaf, and the change's norm per
    leaf after one and after all steps. ``rows`` keeps only the first
    that many rows of each batch (the planted half-batch fault)."""
    import jax

    key = jax.random.PRNGKey(seed)
    params = jax.jit(functools.partial(cfg.init_params, sz))(key)
    step = jax.jit(cfg.ref_step_fn(sz, quantize), donate_argnums=(0,))
    change = jax.jit(lambda p, k: _leaf_norms(jax.tree_util.tree_map(
        lambda a, b: a - b, p, cfg.init_params(sz, k))))
    out = {"losses": []}
    for k, batch in enumerate(cfg.ref_inputs(data, record, sz, seed,
                                             WARM_STEPS, quantize)):
        if rows is not None:
            batch = jax.tree_util.tree_map(lambda a: a[:rows], batch)
        params, loss, norms = step(params, *batch)
        out["losses"].append(float(loss))
        if k == 0:
            out["grad1"] = _host(norms)
            out["change1"] = _host(change(params, key))
    out["change3"] = _host(change(params, key))
    del params
    return out


def check(cfg, data, record, prog, sz, seed, control, log, ref_order):
    """The compared numbers as ``(name, value, limit)``. With ``control``,
    also the control's (``control.*``: the reference in float8 in the
    program's place, and the configuration's planted row faults) and the
    planted half-batch fault's (``fault.half_batch.*``)."""
    import jax

    limits = cfg.LIMITS
    readings = dict(cfg.check_rows(data, record, sz, seed, ref_order))
    with jax.default_matmul_precision("highest"):
        ref = reference_training(cfg, data, record, sz, seed)
        readings.update(compare.training_gaps(prog, ref, sz["learning_rate"]))
        log(f"program losses {prog['losses']} reference {ref['losses']}")
        if control:
            ctl = reference_training(cfg, data, record, sz, seed,
                                     cfg.control_quantize)
            gaps = compare.training_gaps(ctl, ref, sz["learning_rate"])
            gaps.update(cfg.control_rows(data, record, sz, seed, ref_order))
            readings.update({f"control.{k}": v for k, v in gaps.items()})
            half = reference_training(cfg, data, record, sz, seed,
                                      rows=sz["batch_per_chip"] // 2)
            gaps = compare.training_gaps(half, ref, sz["learning_rate"])
            readings.update({f"fault.half_batch.{k}": v
                             for k, v in gaps.items()})
    return [(name, value, limits[name.split(".")[-1]])
            for name, value in readings.items()]
