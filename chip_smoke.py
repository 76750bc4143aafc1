"""Run the main path once on the chip: Parquet → loader → device decode → step.

    python chip_smoke.py              # one chip: every phase below
    python chip_smoke.py --chips 4    # four chips: the sharded-loader phase only

The deployment is BASELINE.md's north star at real width: ImageNet-shaped
PNG rows (375×500×3 uint8, ``examples/imagenet/schema.py``) decoded on the
host, staged as raw uint8, then cropped to 224², flipped, cast to bf16 and
normalized on the device by ``DeviceStage``, feeding the image classifier's
donated SGD step at ``conv_features=64, hidden=2048`` (1.65 B parameters),
batch 128. Weights and data are random, made from ``--seed``.

Phases (one process owns the chip; every child it starts is CPU-only):

1. ``dataset``  — 1,152 seeded rows, 128 per row group, ``materialize_rows``.
2. ``local``    — ``make_columnar_reader`` (thread pool) →
   ``make_jax_dataloader(device_stage=...)`` → 1 warm + 8 steps; every
   array on the chip, the decoded batch equal to ``host_reference``, every
   row group delivered exactly once.
3. ``service``  — a dispatcher plus one ``python -m petastorm_tpu.service
   worker`` process started after the trainer holds the chip; the same
   loader, stage and step for 4 steps; the rows equal the local path's.
4. ``flash``    — ``flash_attention(interpret=False)`` forward + backward at
   [1, 8192, 8, 128] causal bf16, and at [2, 1024, 4, 128] f32 against
   ``attention_reference`` within ``FLASH_TOL``.
5. ``mesh`` (``--chips 4`` only) — a ("data", 2) × ("model", 2) mesh,
   ``sharding=batch_sharding(mesh)`` plus the stage, 4 steps; every batch
   spans all four devices and the losses match the same batches stepped on
   one device within ``MESH_LOSS_RTOL``.

Earlier lines are one JSON object per phase (times, images/s, loss, peak
bytes — information, not benchmark numbers). The last line is
``{"ok": true, "device": {...}}``. Without a TPU the script exits non-zero
before any phase; a phase that raises exits non-zero without that line.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

import numpy as np

REPO_DIR = os.path.dirname(os.path.abspath(__file__))

IMAGENET_MEAN = (0.485 * 255, 0.456 * 255, 0.406 * 255)
IMAGENET_STD = (0.229 * 255, 0.224 * 255, 0.225 * 255)

#: flash vs ``attention_reference`` (both f32, HIGHEST matmul precision):
#: max |a - b| <= atol + rtol * |b|, for the output and all three gradients.
FLASH_TOL = {"atol": 1e-3, "rtol": 1e-3}
#: Device-stage output (bf16) vs its numpy ``host_reference``: one bf16
#: rounding of ``(x - mean)`` before ``* 1/std`` may differ between the two.
STAGE_ATOL = 2.0 ** -5
#: Sharded vs one-device loss, same batches and init: bf16 matmuls reduce
#: in a different order across the model axis.
MESH_LOSS_RTOL = 2e-2


@dataclasses.dataclass(frozen=True)
class Size:
    rows: int
    image_shape: tuple
    crop: tuple
    batch: int
    conv_features: int
    hidden: int
    num_classes: int
    service_steps: int
    mesh_steps: int
    flash_long: tuple   # [B, T, H, D], causal bf16, forward + backward
    flash_ref: tuple    # [B, T, H, D], f32 against attention_reference
    reader_workers: int


FULL = Size(rows=1152, image_shape=(375, 500, 3), crop=(224, 224), batch=128,
            conv_features=64, hidden=2048, num_classes=1000,
            service_steps=4, mesh_steps=4,
            flash_long=(1, 8192, 8, 128), flash_ref=(2, 1024, 4, 128),
            reader_workers=8)


def peak_bytes(device):
    stats = device.memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def emit(record, devices=()):
    """Print one phase record, with the kind and peak HBM of its devices."""
    if devices:
        record["device_kind"] = devices[0].device_kind
        peaks = [peak_bytes(d) for d in devices]
        record["peak_bytes_in_use"] = peaks[0] if len(peaks) == 1 else peaks
    print(json.dumps(record), flush=True)


# -- 1. dataset ---------------------------------------------------------------

def make_image(seed, index, shape):
    """A smooth seeded image plus mild noise: PNG sizes near a photo's."""
    import cv2

    rng = np.random.default_rng([seed, index])
    h, w, c = shape
    low = rng.integers(0, 256, (max(2, h // 64), max(2, w // 64), c),
                       dtype=np.uint8)
    smooth = cv2.resize(low, (w, h), interpolation=cv2.INTER_CUBIC)
    noisy = smooth + rng.normal(0.0, 3.0, shape)
    return np.clip(noisy, 0, 255).astype(np.uint8)


def make_labels(seed, size):
    rng = np.random.default_rng(seed)
    return rng.integers(0, size.num_classes, size.rows).astype(np.int32)


def phase_dataset(url, size, seed):
    """Write ``size.rows`` rows, one row group per batch; return the labels
    (row order) for the exactly-once checks."""
    from petastorm_tpu.etl.metadata import materialize_rows
    from petastorm_tpu.schema.codecs import CompressedImageCodec, ScalarCodec
    from petastorm_tpu.schema.unischema import Unischema, UnischemaField

    schema = Unischema("ChipSmokeSchema", [
        # The image field of examples/imagenet/schema.py at its shape.
        UnischemaField("image", np.uint8, size.image_shape,
                       CompressedImageCodec("png"), False),
        UnischemaField("label", np.int32, (), ScalarCodec(), False),
    ])
    labels = make_labels(seed, size)
    rows = ({"image": make_image(seed, i, size.image_shape),
             "label": labels[i]} for i in range(size.rows))
    t0 = time.perf_counter()
    materialize_rows(url, schema, rows, rows_per_row_group=size.batch,
                     encode_workers=os.cpu_count() or 1)
    path = url[len("file://"):]
    nbytes = sum(os.path.getsize(os.path.join(d, f))
                 for d, _, files in os.walk(path) for f in files)
    emit({"phase": "dataset", "rows": size.rows,
          "row_groups": size.rows // size.batch,
          "wall_s": time.perf_counter() - t0, "bytes": nbytes})
    return labels


# -- 2. local loader + train step ---------------------------------------------

def make_stage(size, seed):
    import jax.numpy as jnp

    from petastorm_tpu.jax_utils import DeviceStage

    return DeviceStage(image_fields=("image",), crop=size.crop, flip=True,
                       normalize=(IMAGENET_MEAN, IMAGENET_STD),
                       output_dtype=jnp.bfloat16, seed=seed)


def init_model(size, seed, out_shardings=None):
    import jax

    from petastorm_tpu.models.image_classifier import init_params

    init = functools.partial(init_params, image_shape=size.crop + (3,),
                             num_classes=size.num_classes, hidden=size.hidden,
                             conv_features=size.conv_features)
    return jax.jit(init, out_shardings=out_shardings)(
        jax.random.PRNGKey(seed))


def compile_step(step, params, mask, size, batch_sharding):
    """AOT-compile the train step for this run's batch shapes: returns the
    executable and its compile seconds."""
    import jax
    import jax.numpy as jnp

    images = jax.ShapeDtypeStruct((size.batch,) + size.crop + (3,),
                                  jnp.bfloat16, sharding=batch_sharding)
    labels = jax.ShapeDtypeStruct((size.batch,), jnp.int32,
                                  sharding=batch_sharding)
    t0 = time.perf_counter()
    compiled = step.lower(params, images, labels, mask).compile()
    return compiled, time.perf_counter() - t0


def piece_of(labels, batch_labels, size):
    """The row group a delivered batch carries, found by its label column."""
    for piece in range(size.rows // size.batch):
        expect = labels[piece * size.batch:(piece + 1) * size.batch]
        if np.array_equal(expect, batch_labels):
            return piece
    raise AssertionError("a delivered batch matches no row group's labels")


def check_on_device(batch, device):
    for name, value in batch.items():
        devices = value.devices()
        assert devices == {device}, \
            f"field {name!r} lives on {devices}, expected {{{device}}}"


def check_stage_output(stage, images, piece, step, size, seed):
    """The decoded batch equals the stage's numpy reference on the raw rows
    of the row group it carries (PNG is lossless: those are the generated
    images)."""
    raw = np.stack([make_image(seed, i, size.image_shape) for i in
                    range(piece * size.batch, (piece + 1) * size.batch)])
    want = stage.host_reference({"image": raw}, step)["image"]
    got = np.asarray(images)
    assert got.shape == want.shape and got.dtype == want.dtype, \
        (got.shape, got.dtype, want.shape, want.dtype)
    err = float(np.max(np.abs(got.astype(np.float32)
                              - want.astype(np.float32))))
    assert err <= STAGE_ATOL, f"device stage off its reference by {err}"
    return err


def run_steps(loader, step, params, mask, labels, size, seed, stage,
              device, phase):
    """Drive ``loader`` through the compiled ``step``: 1 warm step, then one
    step per remaining batch. Returns (params, record, last batch)."""
    import jax

    pieces, losses, step_s = [], [], []
    first_batch_s = stage_err = None
    t_start = time.perf_counter()
    with loader:
        for i, batch in enumerate(loader):
            if i == 0:
                first_batch_s = time.perf_counter() - t_start
            check_on_device(batch, device)
            labels_host = np.asarray(batch["label"])
            pieces.append(piece_of(labels, labels_host, size))
            if i == 0:
                stage_err = check_stage_output(stage, batch["image"],
                                               pieces[0], 0, size, seed)
            t0 = time.perf_counter()
            params, loss = step(params, batch["image"], batch["label"], mask)
            loss = float(jax.block_until_ready(loss))
            step_s.append(time.perf_counter() - t0)
            assert np.isfinite(loss), f"{phase} step {i}: loss {loss}"
            losses.append(loss)
            t_last = time.perf_counter()
            if i == 0:
                t_steady = t_last
    steady = step_s[1:]
    n_steady = len(steady)
    wall = time.perf_counter() - t_start
    record = {
        "phase": phase, "steps": len(losses), "warm_steps": 1,
        "first_batch_s": first_batch_s, "warm_step_s": step_s[0],
        "steady_step_s_median": float(np.median(steady)) if steady else None,
        "images_per_s": (n_steady * size.batch / (t_last - t_steady)
                         if n_steady else None),
        "loss_first": losses[0], "loss_last": losses[-1],
        "stage_max_abs_err": stage_err, "wall_s": wall, "pieces": pieces}
    return params, record, batch


def phase_local(url, size, seed, labels, device):
    import jax
    import jax.numpy as jnp

    from jax.sharding import SingleDeviceSharding

    from petastorm_tpu import make_columnar_reader
    from petastorm_tpu.jax_utils import make_jax_dataloader
    from petastorm_tpu.models.image_classifier import make_train_step

    t0 = time.perf_counter()
    params = jax.block_until_ready(init_model(size, seed))
    init_s = time.perf_counter() - t0
    mask = jax.device_put(jnp.ones((size.batch,), bool), device)
    step, compile_s = compile_step(
        jax.jit(make_train_step(), donate_argnums=(0,)), params, mask, size,
        SingleDeviceSharding(device))
    stage = make_stage(size, seed)
    reader = make_columnar_reader(url, reader_pool_type="thread",
                                  workers_count=size.reader_workers,
                                  shuffle_row_groups=False, num_epochs=1)
    loader = make_jax_dataloader(reader, size.batch, device_stage=stage)
    params, record, last = run_steps(loader, step, params, mask, labels,
                                     size, seed, stage, device, "local")
    n_pieces = size.rows // size.batch
    assert sorted(record["pieces"]) == list(range(n_pieces)), \
        f"row groups not delivered exactly once: {record['pieces']}"
    record["rows"] = len(record["pieces"]) * size.batch
    assert record["rows"] == size.rows, (record["rows"], size.rows)
    # The step alone, on the last batch with the loader closed: what the
    # steady step time costs without input work beside it (not a training
    # step of the epoch — the loss is discarded).
    isolated = []
    for _ in range(3):
        t0 = time.perf_counter()
        params, loss = step(params, last["image"], last["label"], mask)
        jax.block_until_ready(loss)
        isolated.append(time.perf_counter() - t0)
    record.update(init_s=init_s, compile_s=compile_s,
                  isolated_step_s_median=float(np.median(isolated)))
    emit(record, [device])
    return params, step, mask


# -- 3. service: dispatcher + a CPU-only worker process -----------------------

def worker_args(dispatcher_address, url, size):
    host, port = dispatcher_address
    return ["worker", "--dispatcher", f"{host}:{port}", "--dataset-url", url,
            "--reader", "columnar", "--batch-size", str(size.batch),
            "--workers-count", str(size.reader_workers)]


def start_worker(dispatcher_address, url, size):
    """``python -m petastorm_tpu.service worker`` as its own process; returns
    it once it has registered (its first stdout line)."""
    proc = subprocess.Popen(
        [sys.executable, "-m", "petastorm_tpu.service",
         *worker_args(dispatcher_address, url, size)],
        cwd=REPO_DIR, stdout=subprocess.PIPE, text=True)
    return proc, read_hello(proc)


def read_hello(proc):
    line = proc.stdout.readline()
    if not line:
        stop_worker(proc)
        raise RuntimeError(f"service worker exited (rc={proc.returncode}) "
                           "before registering")
    return json.loads(line)


def stop_worker(proc):
    if proc.poll() is None:
        proc.terminate()
        try:
            proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
    proc.stdout.close()
    return proc.returncode


def phase_service(url, size, seed, labels, device, params, step, mask):
    from petastorm_tpu.jax_utils import JaxDataLoader
    from petastorm_tpu.service import Dispatcher, ServiceBatchSource

    t0 = time.perf_counter()
    dispatcher = Dispatcher(port=0, mode="static", num_epochs=1).start()
    proc = None
    try:
        proc, hello = start_worker(dispatcher.address, url, size)
        started_s = time.perf_counter() - t0
        source = ServiceBatchSource(dispatcher.address, ordered=True)
        stage = make_stage(size, seed)
        loader = JaxDataLoader(None, size.batch, batch_source=source,
                               max_batches=size.service_steps,
                               device_stage=stage)
        params, record, _ = run_steps(loader, step, params, mask, labels,
                                      size, seed, stage, device, "service")
    finally:
        worker_rc = stop_worker(proc) if proc is not None else None
        dispatcher.stop()
    # ordered=True delivers row groups in ascending order: the first
    # ``service_steps`` of them, row for row (``piece_of``), as the local
    # path carried them.
    want = list(range(size.service_steps))
    assert record["pieces"] == want, (record["pieces"], want)
    assert worker_rc == 0, f"service worker exited rc={worker_rc}"
    record.update(worker_start_s=started_s, worker_id=hello.get("worker_id"),
                  worker_rc=worker_rc)
    emit(record, [device])
    return params


# -- 4. flash attention kernel ------------------------------------------------

def _attention_loss(attn, q, k, v, do):
    import jax.numpy as jnp

    return jnp.sum(attn(q, k, v).astype(jnp.float32) * do)


def phase_flash(size, seed, interpret, device):
    import jax
    import jax.numpy as jnp

    from petastorm_tpu.models.sequence_model import attention_reference
    from petastorm_tpu.ops.flash_attention import flash_attention

    flash = functools.partial(flash_attention, causal=True,
                              interpret=interpret)
    keys = jax.random.split(jax.random.PRNGKey(seed), 8)

    # Long causal bf16: forward + backward, finite.
    shape = size.flash_long
    q, k, v = (jax.random.normal(keys[i], shape, jnp.bfloat16)
               for i in range(3))
    do = jax.random.normal(keys[3], shape, jnp.float32)
    fwd_bwd = jax.jit(jax.value_and_grad(
        functools.partial(_attention_loss, flash), argnums=(0, 1, 2)))
    t0 = time.perf_counter()
    compiled = fwd_bwd.lower(q, k, v, do).compile()
    compile_s = time.perf_counter() - t0
    jax.block_until_ready(compiled(q, k, v, do))  # warm
    t0 = time.perf_counter()
    loss, grads = jax.block_until_ready(compiled(q, k, v, do))
    step_s = time.perf_counter() - t0
    assert np.isfinite(float(loss)), float(loss)
    for name, g in zip("qkv", grads):
        assert g.shape == shape and bool(jnp.all(jnp.isfinite(g))), name
    long_record = {"shape": list(shape), "dtype": "bfloat16", "causal": True,
                   "compile_s": compile_s, "fwd_bwd_s": step_s}

    # f32 against the dense reference, forward and gradients.
    shape = size.flash_ref
    q, k, v = (jax.random.normal(keys[4 + i], shape, jnp.float32)
               for i in range(3))
    do = jax.random.normal(keys[7], shape, jnp.float32)
    with jax.default_matmul_precision("highest"):
        outs = {}
        for name, attn in (("flash", flash),
                           ("reference", functools.partial(
                               attention_reference, causal=True))):
            out = jax.jit(attn)(q, k, v)
            grads = jax.jit(jax.grad(
                functools.partial(_attention_loss, attn),
                argnums=(0, 1, 2)))(q, k, v, do)
            outs[name] = [np.asarray(x) for x in (out,) + tuple(grads)]
    errs = {}
    for name, got, want in zip(("out", "dq", "dk", "dv"), outs["flash"],
                               outs["reference"]):
        excess = np.abs(got - want) - FLASH_TOL["rtol"] * np.abs(want)
        errs[name] = float(np.max(np.abs(got - want)))
        assert np.all(excess <= FLASH_TOL["atol"]), \
            f"flash {name} off attention_reference by {errs[name]}"
    emit({"phase": "flash", "interpret": interpret, "long": long_record,
          "reference": {"shape": list(shape), "dtype": "float32",
                        "causal": True, "tol": FLASH_TOL,
                        "max_abs_err": errs}}, [device])


# -- 5. four chips: sharded loader + data/tensor-parallel step ----------------

def phase_mesh(url, size, seed, labels, devices):
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from jax.sharding import SingleDeviceSharding

    from petastorm_tpu import make_columnar_reader
    from petastorm_tpu.jax_utils import batch_sharding, make_jax_dataloader
    from petastorm_tpu.models.image_classifier import (make_train_step,
                                                       param_partition_specs)

    mesh = Mesh(np.array(devices).reshape(2, 2), ("data", "model"))
    param_sh = jax.tree_util.tree_map(
        lambda spec: NamedSharding(mesh, spec), param_partition_specs(),
        is_leaf=lambda x: isinstance(x, P))
    batch_sh = batch_sharding(mesh)
    step = jax.jit(make_train_step(),
                   in_shardings=(param_sh, batch_sh, batch_sh, batch_sh),
                   out_shardings=(param_sh, NamedSharding(mesh, P())),
                   donate_argnums=(0,))
    params = init_model(size, seed, out_shardings=param_sh)
    mask = jax.device_put(jnp.ones((size.batch,), bool), batch_sh)
    step, compile_s = compile_step(step, params, mask, size, batch_sh)
    reader = make_columnar_reader(url, reader_pool_type="thread",
                                  workers_count=size.reader_workers,
                                  shuffle_row_groups=False, num_epochs=1)
    loader = make_jax_dataloader(reader, size.batch, sharding=batch_sh,
                                 max_batches=size.mesh_steps,
                                 device_stage=make_stage(size, seed))
    want_devices = set(devices)
    batches, losses, step_s = [], [], []
    t_start = time.perf_counter()
    with loader:
        for batch in loader:
            for name, value in batch.items():
                assert value.sharding.device_set == want_devices, \
                    f"{name} spans {value.sharding.device_set}"
            batches.append({k: np.asarray(v) for k, v in batch.items()})
            piece_of(labels, batches[-1]["label"], size)
            t0 = time.perf_counter()
            params, loss = step(params, batch["image"], batch["label"], mask)
            losses.append(float(jax.block_until_ready(loss)))
            step_s.append(time.perf_counter() - t0)
    wall = time.perf_counter() - t_start
    assert len(losses) == size.mesh_steps, losses
    del params, step

    # The same batches, init and step on one device of this process.
    one = SingleDeviceSharding(devices[0])
    params = init_model(size, seed, out_shardings=one)
    step = jax.jit(make_train_step(), donate_argnums=(0,))
    mask = jax.device_put(jnp.ones((size.batch,), bool), devices[0])
    ref_losses = []
    for host in batches:
        images, batch_labels = jax.device_put(
            (host["image"], host["label"]), devices[0])
        params, loss = step(params, images, batch_labels, mask)
        ref_losses.append(float(jax.block_until_ready(loss)))
    del params
    np.testing.assert_allclose(losses, ref_losses, rtol=MESH_LOSS_RTOL)
    emit({"phase": "mesh", "mesh": {"data": 2, "model": 2},
          "steps": len(losses), "compile_s": compile_s,
          "warm_step_s": step_s[0],
          "steady_step_s_median": (float(np.median(step_s[1:]))
                                   if len(step_s) > 1 else None),
          "losses": losses, "one_device_losses": ref_losses,
          "loss_rtol": MESH_LOSS_RTOL, "wall_s": wall}, devices)


# -- driver --------------------------------------------------------------------

def run(size, seed, chips, interpret):
    """All phases of one ``--chips`` mode at ``size`` in a temp directory."""
    import jax

    devices = jax.devices()
    tmpdir = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        url = f"file://{os.path.join(tmpdir, 'ds')}"
        labels = phase_dataset(url, size, seed)
        if chips == 4:
            phase_mesh(url, size, seed, labels, devices[:4])
            return
        params, step, mask = phase_local(url, size, seed, labels, devices[0])
        params = phase_service(url, size, seed, labels, devices[0], params,
                               step, mask)
        del params, step
        phase_flash(size, seed, interpret, devices[0])
    finally:
        shutil.rmtree(tmpdir, ignore_errors=True)


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU: JAX's first device is "
              f"{devices[0].platform!r} ({devices[0].device_kind}); this "
              f"script runs only on a TPU chip", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 2

    from petastorm_tpu.jax_utils.compile_cache import use_compile_cache

    cache_dir = use_compile_cache()
    t0 = time.perf_counter()
    emit({"phase": "start", "device_kind": devices[0].device_kind,
          "device_count": len(devices), "jax": jax.__version__,
          "compile_cache_dir": cache_dir, "host_cores": os.cpu_count()})
    run(FULL, args.seed, args.chips, interpret=False)
    emit({"phase": "done", "wall_s": time.perf_counter() - t0})
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
