"""ImageNet-1k training input: seeded JPEG rows, the consumer step, its
operation counts, and the plain reference that decides ``correct``.

The sizes are in ``imagenet1k.json`` beside this file. The reference here
imports nothing of ``petastorm_tpu``: it decodes the JPEG bytes this file
wrote with PIL (the program decodes with OpenCV), crops, flips and
normalizes in float32 numpy, and trains the same three-layer network in
float32 at HIGHEST matmul precision with a hand-written backward pass.
"""

import io
import json
import math
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

SIZES_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "imagenet1k.json")

#: Batches whose images the check compares, besides the three warm-up steps:
#: this many steps drawn from the seed among the window's first
#: ``SAMPLE_RANGE`` steps (every window holds more).
SAMPLES = 4
SAMPLE_RANGE = 48


def load_sizes(overrides=None):
    with open(SIZES_FILE) as f:
        sz = json.load(f)
    sz.update(overrides or {})
    return sz


# -- data ------------------------------------------------------------------

def _pool_image(sz, seed, index):
    """One smooth seeded image with noise, JPEG-encoded: the noise and
    quality put the mean file near ``jpeg_mean_bytes_target``."""
    import cv2

    h, w, c = sz["image_height"], sz["image_width"], sz["channels"]
    rng = np.random.default_rng([seed, 7, index])
    low = rng.integers(0, 256, (max(2, h // 32), max(2, w // 32), c),
                       dtype=np.uint8)
    img = cv2.resize(low, (w, h), interpolation=cv2.INTER_CUBIC)
    img = img.astype(np.float32)
    img += rng.standard_normal((h, w, c), dtype=np.float32) * sz["jpeg_noise"]
    img = np.clip(img, 0, 255).astype(np.uint8)
    ok, enc = cv2.imencode(".jpg", img,
                           [int(cv2.IMWRITE_JPEG_QUALITY), sz["jpeg_quality"]])
    assert ok
    return enc.tobytes()


class Data:
    """The dataset as written, and what the reference needs to rebuild any
    row: ``pool[perm[row]]`` is row ``row``'s JPEG, ``labels[row]`` its
    label."""

    def __init__(self, url, pool, perm, labels, nbytes):
        self.url, self.pool, self.perm = url, pool, perm
        self.labels, self.nbytes = labels, nbytes


def make_dataset(path, sz, seed, write=True):
    """The seed's rows; written to ``path`` as Parquet when ``write``."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    from petastorm_tpu.etl.metadata import materialize_dataset
    from petastorm_tpu.schema.codecs import CompressedImageCodec, ScalarCodec
    from petastorm_tpu.schema.unischema import Unischema, UnischemaField

    rows, pool_n = sz["rows"], sz["image_pool"]
    with ThreadPoolExecutor(os.cpu_count() or 1) as ex:
        pool = list(ex.map(lambda i: _pool_image(sz, seed, i), range(pool_n)))
    rng = np.random.default_rng([seed, 11])
    reps = -(-rows // pool_n)
    perm = np.concatenate([rng.permutation(pool_n) for _ in range(reps)])[:rows]
    labels = rng.integers(0, sz["num_classes"], rows).astype(np.int32)
    url = "file://" + os.path.abspath(path)
    nbytes = sum(len(pool[p]) for p in perm)
    if not write:
        return Data(url, pool, perm, labels, nbytes)
    schema = Unischema("Imagenet1kBench", [
        UnischemaField("image", np.uint8,
                       (sz["image_height"], sz["image_width"], sz["channels"]),
                       CompressedImageCodec("jpeg", sz["jpeg_quality"]), False),
        UnischemaField("label", np.int32, (), ScalarCodec(), False),
        UnischemaField("row_id", np.int64, (), ScalarCodec(), False),
    ])
    arrow_schema = schema.as_arrow_schema()
    group = sz["rows_per_row_group"]
    with materialize_dataset(None, url, schema):
        os.makedirs(path, exist_ok=True)
        with pq.ParquetWriter(os.path.join(path, "part-00000.parquet"),
                              arrow_schema, compression="none") as writer:
            for g0 in range(0, rows, group):
                idx = range(g0, min(rows, g0 + group))
                writer.write_table(pa.Table.from_arrays([
                    pa.array([pool[perm[r]] for r in idx], pa.binary()),
                    pa.array(labels[g0:g0 + len(idx)], pa.int32()),
                    pa.array(np.arange(g0, g0 + len(idx)), pa.int64()),
                ], schema=arrow_schema), row_group_size=len(idx))
    return Data(url, pool, perm, labels, nbytes)


def make_reader(data, sz, seed, **kwargs):
    from petastorm_tpu import make_columnar_reader

    return make_columnar_reader(
        data.url, reader_pool_type=sz["reader_pool"],
        workers_count=sz["reader_workers"], shuffle_row_groups=True,
        shard_seed=seed, num_epochs=None, **kwargs)


def device_stage(sz, seed):
    import jax.numpy as jnp

    from petastorm_tpu.jax_utils import DeviceStage

    return DeviceStage(image_fields=("image",), crop=(sz["crop"], sz["crop"]),
                       flip=sz["flip"], normalize=(sz["mean"], sz["std"]),
                       output_dtype=jnp.dtype(sz["stage_dtype"]),
                       seed=stage_seed(seed))


def stage_seed(seed):
    return seed % (2 ** 31)


# -- consumer step ---------------------------------------------------------

def init_params(sz, key):
    """The image classifier's parameters from ``key``: the arithmetic of
    ``models/image_classifier.py::init_params``, kept here so the weights
    are the benchmark's and the reference rebuilds them alike."""
    import jax
    import jax.numpy as jnp

    c, f, hidden = sz["channels"], sz["conv_features"], sz["hidden"]
    flat = (sz["crop"] // 2) * (sz["crop"] // 2) * f
    k_conv, k_w1, k_w2 = jax.random.split(key, 3)
    return {
        "conv": {"kernel": jax.random.normal(k_conv, (3, 3, c, f), jnp.float32)
                 * (1.0 / jnp.sqrt(9.0 * c)),
                 "bias": jnp.zeros((f,), jnp.float32)},
        "dense1": {"kernel": jax.random.normal(k_w1, (flat, hidden),
                                               jnp.float32)
                   * (1.0 / jnp.sqrt(float(flat))),
                   "bias": jnp.zeros((hidden,), jnp.float32)},
        "dense2": {"kernel": jax.random.normal(
            k_w2, (hidden, sz["num_classes"]), jnp.float32)
            * (1.0 / jnp.sqrt(float(hidden))),
            "bias": jnp.zeros((sz["num_classes"],), jnp.float32)},
    }


def make_step(sz):
    """``step(params, batch) -> (params, loss)``: the program's SGD step on
    the loader's batch dict."""
    import jax.numpy as jnp

    from petastorm_tpu.models.image_classifier import make_train_step

    train = make_train_step(sz["learning_rate"])

    def step(params, batch):
        images, labels = batch["image"], batch["label"]
        return train(params, images, labels,
                     jnp.ones(labels.shape, bool))

    return step


def step_flops(sz, batch):
    """Forward + backward operations of one step: conv, dense1, dense2;
    backward is twice the forward, less the conv's input gradient (the
    first layer has none). Pooling, bias and activation are left out."""
    c, f, hidden = sz["channels"], sz["conv_features"], sz["hidden"]
    crop = sz["crop"]
    flat = (crop // 2) ** 2 * f
    conv = 2 * crop * crop * f * 9 * c
    dense = 2 * flat * hidden + 2 * hidden * sz["num_classes"]
    return batch * (3 * dense + 2 * conv)


def input_bytes(sz, batch):
    """Bytes the device-side input work must move per step: read the staged
    uint8 batch once, write the cropped batch in the stage dtype once."""
    import jax.numpy as jnp

    raw = batch * sz["image_height"] * sz["image_width"] * sz["channels"]
    out = batch * sz["crop"] ** 2 * sz["channels"] \
        * jnp.dtype(sz["stage_dtype"]).itemsize
    return raw + out


# -- what the window keeps for the check -----------------------------------

class Record:
    """Keeps, per delivered batch, its row ids (every batch) and the images
    of the warm-up batches and of ``SAMPLES`` window batches drawn from the
    seed; hands them to the host after the window."""

    def __init__(self, sz, seed, warm):
        rng = np.random.default_rng([seed, 13])
        self.sample = set(range(warm)) | set(
            int(k) for k in warm + rng.choice(SAMPLE_RANGE, SAMPLES,
                                              replace=False))
        self.ids, self.images = [], {}

    def keep(self, k, batch):
        self.ids.append(batch["row_id"])
        if k in self.sample:
            self.images[k] = batch["image"]

    def to_host(self):
        import jax

        self.ids = np.stack([np.asarray(a) for a in jax.device_get(self.ids)])
        self.images = {k: np.asarray(jax.device_get(v).astype(np.float32))
                       for k, v in self.images.items()}


# -- the plain reference ---------------------------------------------------

def ref_decode(data, rows):
    """Decode with PIL (RGB), turned to the stored channel order (OpenCV's
    BGR, as the array was encoded)."""
    from PIL import Image

    return np.stack([np.asarray(Image.open(io.BytesIO(data.pool[data.perm[r]])))
                     [:, :, ::-1] for r in rows])


def ref_stage(raw, sz, seed, step, quantize=None):
    """Crop, flip and normalize a raw uint8 batch in float32: the crop
    offsets and flips are drawn as the stage's seed, step and field
    ordinal give them (``jax.random``: fold_in(seed, step), fold_in(0),
    split for the crop, split for the flip)."""
    import jax

    b, h, w = raw.shape[:3]
    ch = cw = sz["crop"]
    key = jax.random.fold_in(jax.random.PRNGKey(stage_seed(seed)), step)
    key = jax.random.fold_in(key, 0)
    key, crop_key = jax.random.split(key)
    offsets = np.asarray(jax.random.randint(
        crop_key, (b, 2), 0, np.asarray([h - ch + 1, w - cw + 1])))
    key, flip_key = jax.random.split(key)
    flips = np.asarray(jax.random.bernoulli(flip_key, 0.5, (b,)))
    out = np.stack([img[o[0]:o[0] + ch, o[1]:o[1] + cw] for img, o in
                    zip(raw, offsets)]).astype(np.float32)
    out[flips] = out[flips][:, :, ::-1]
    out = (out - np.float32(sz["mean"])) / np.float32(sz["std"])
    return out if quantize is None else np.asarray(quantize(out))


def _conv_pool(wc, bc, x, q):
    import jax
    import jax.numpy as jnp

    y = jax.lax.conv_general_dilated(
        q(x), q(wc), (1, 1), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST)
    y = jax.nn.relu(y + bc)
    b, h, w, f = y.shape
    return y.reshape(b, h // 2, 2, w // 2, 2, f).mean(axis=(2, 4)).reshape(b, -1)


def ref_step_fn(sz, quantize, block=16):
    """One float32 SGD step of the classifier, backward written out:
    ``(params, images, labels) -> (params, loss, grad norms)``. The conv
    runs ``block`` images at a time so its activations stay small; the
    dense-1 gradient is the only full-size temporary."""
    import jax
    import jax.numpy as jnp

    lr = sz["learning_rate"]
    hi = jax.lax.Precision.HIGHEST
    q = quantize or (lambda x: x)

    def dot(a, b):
        return jnp.dot(q(a), q(b), precision=hi)

    def step(params, images, labels):
        wc, bc = params["conv"]["kernel"], params["conv"]["bias"]
        w1, b1 = params["dense1"]["kernel"], params["dense1"]["bias"]
        w2, b2 = params["dense2"]["kernel"], params["dense2"]["bias"]
        n = images.shape[0]
        block_n = math.gcd(n, block)
        blocks = images.reshape((n // block_n, block_n) + images.shape[1:])
        x1 = jax.lax.map(lambda xb: _conv_pool(wc, bc, xb, q),
                         blocks).reshape(n, -1)
        z = dot(x1, w1) + b1
        h = jax.nn.relu(z)
        logits = dot(h, w2) + b2
        logp = jax.nn.log_softmax(logits)
        loss = -jnp.mean(jnp.take_along_axis(logp, labels[:, None], 1))
        dlogits = (jnp.exp(logp) - jax.nn.one_hot(labels, logits.shape[1])) / n
        dw2, db2 = dot(h.T, dlogits), dlogits.sum(0)
        dz = dot(dlogits, w2.T) * (z > 0)
        dw1, db1 = dot(x1.T, dz), dz.sum(0)
        dx1 = dot(dz, w1.T).reshape((n // block_n, block_n, -1))

        def conv_grads(args):
            xb, gb = args
            _, vjp = jax.vjp(lambda k, b: _conv_pool(k, b, xb, q), wc, bc)
            return vjp(gb)

        dwc, dbc = jax.lax.map(conv_grads, (blocks, dx1))
        grads = {"conv": {"kernel": dwc.sum(0), "bias": dbc.sum(0)},
                 "dense1": {"kernel": dw1, "bias": db1},
                 "dense2": {"kernel": dw2, "bias": db2}}
        norms = jax.tree_util.tree_map(lambda g: jnp.sqrt(jnp.sum(g * g)),
                                       grads)
        new = jax.tree_util.tree_map(lambda p, g: p - lr * g, params, grads)
        return new, loss, norms

    return step


def ref_inputs(data, record, sz, seed, steps, quantize=None):
    """The reference's own batches for the first ``steps`` steps: rows by
    the ids the window delivered, decoded and staged by the reference."""
    for k in range(steps):
        rows = record.ids[k]
        yield (ref_stage(ref_decode(data, rows), sz, seed, k, quantize),
               data.labels[rows])


def check_rows(data, record, sz, seed, ref_order):
    """Numbers for the rows, their order (``ref_order(seed, groups,
    epochs)``, the delivery path's) and the decoded, staged pixels."""
    from harness.compare import order_readings

    group = sz["rows_per_row_group"]
    ids = record.ids
    # A batch carries whole row groups, one after another.
    chunks = ids.reshape(-1, group)
    starts = chunks[:, 0] // group
    not_groups = int(np.sum(np.any(
        chunks != starts[:, None] * group + np.arange(group), axis=1)))
    epochs = -(-len(starts) * group // sz["rows"]) + 1
    groups = sz["rows"] // group
    order = order_readings(list(starts), ref_order(seed, groups, epochs),
                           groups)
    stage_gap = 0.0
    for k, got in record.images.items():
        want = ref_stage(ref_decode(data, ids[k]), sz, seed, k)
        stage_gap = max(stage_gap, float(np.max(np.abs(got - want))))
    return {"batches_not_row_groups": not_groups, **order,
            "stage_max_gap": stage_gap}


def control_stage_gap(data, record, sz, seed, quantize):
    gap = 0.0
    for k in record.images:
        raw = ref_decode(data, record.ids[k])
        got = ref_stage(raw, sz, seed, k, quantize)
        gap = max(gap, float(np.max(np.abs(got - ref_stage(raw, sz, seed, k)))))
    return gap


# -- limits and the control --------------------------------------------------

#: Each compared number's limit, set between the largest reading of sound
#: runs over a dozen seeds and more and the smallest of the control or of a
#: planted fault, on the chip at the cell's size: readings in PERF.md.
LIMITS = {
    "batches_not_row_groups": 0,
    "order_mean_lag": 8.0,
    "rows_once_violations": 0,
    "stage_max_gap": 0.05,
    "loss_gap": 0.002,
    "grad1_gap": 0.05,
    "change3_gap": 0.05,
}


def control_quantize(x):
    """The control's precision: float8 e4m3 with one scale per tensor."""
    from harness.precision import fp8

    return fp8(x)


def control_rows(data, record, sz, seed, ref_order):
    """The control's row numbers: its staged pixels in float8, and the order
    numbers of the reference's rows with another seed's order or with a
    row group delivered twice put in the program's place."""
    from harness.compare import order_faults

    groups = sz["rows"] // sz["rows_per_row_group"]
    n = record.ids.size // sz["rows_per_row_group"]
    epochs = -(-n * sz["rows_per_row_group"] // sz["rows"]) + 1
    return {"stage_max_gap": control_stage_gap(data, record, sz, seed,
                                               control_quantize),
            **order_faults(ref_order(seed, groups, epochs),
                           ref_order(seed + 1, groups, epochs), n, groups)}
