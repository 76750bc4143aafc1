"""Criteo 1TB click logs as MLPerf DLRM reads them: seeded plain-Parquet
rows, the DLRM consumer step, its operation counts, and the plain
reference that decides ``correct``.

The sizes are in ``criteo1tb.json`` beside this file. The reference here
imports nothing of ``petastorm_tpu``: its rows are the arrays this file
wrote, and it trains the same DLRM in float32 at HIGHEST matmul precision.
"""

import json
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

SIZES_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "criteo1tb.json")

#: Window batches whose every value the check compares, drawn from the seed
#: among the window's first ``SAMPLE_RANGE`` steps, besides the warm-up's.
SAMPLES = 8
SAMPLE_RANGE = 200


def load_sizes(overrides=None):
    with open(SIZES_FILE) as f:
        sz = json.load(f)
    sz.update(overrides or {})
    return sz


def dense_names(sz):
    return [f"I{i + 1}" for i in range(sz["num_dense"])]


def sparse_names(sz):
    return [f"C{i + 1}" for i in range(sz["num_sparse"])]


# -- data ------------------------------------------------------------------

class Data:
    """The rows as written: ``label [N]``, ``dense [N, 13]``,
    ``sparse [N, 26]`` in row order; ``nbytes`` of Parquet."""

    def __init__(self, url, label, dense, sparse, nbytes):
        self.url, self.label, self.dense, self.sparse = url, label, dense, sparse
        self.nbytes = nbytes


def _zipf_ids(rng, n, top, a):
    """Ids in [0, top) with a Zipf-like tail of exponent ``a`` (inverse CDF
    of a continuous power law on [1, top + 1)), scattered over the range by
    a prime multiplier so the hot ids are not the small ones."""
    u = rng.random(n)
    x = (((top + 1) ** (1 - a) - 1) * u + 1) ** (1 / (1 - a))
    ids = np.minimum(x.astype(np.int64) - 1, top - 1)
    return (ids * 2654435761 + rng.integers(0, top)) % top


def make_dataset(path, sz, seed, write=True):
    """The seed's rows; written to ``path`` as Parquet when ``write``."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    n = sz["rows"]
    rng = np.random.default_rng([seed, 21])
    label = (rng.random(n) < sz["click_rate"]).astype(np.int32)

    def dense_column(i):
        # log(1 + x), x a heavy-tailed count (MLPerf's preprocessing of the
        # integer features).
        r = np.random.default_rng([seed, 22, i])
        return np.log1p(r.geometric(r.uniform(0.002, 0.3), n) - 1)

    def sparse_column(i):
        # One id per row over the feature's own published cardinality.
        r = np.random.default_rng([seed, 23, i])
        top = min(sz["ids_per_feature"][i], sz["max_ind_range"])
        return _zipf_ids(r, n, top, sz["zipf_exponent"])

    with ThreadPoolExecutor(os.cpu_count() or 1) as ex:
        dense = np.stack(list(ex.map(dense_column, range(sz["num_dense"]))),
                         axis=1).astype(np.float32)
        sparse = np.stack(list(ex.map(sparse_column,
                                      range(sz["num_sparse"]))), axis=1)
    file = os.path.join(path, "part-00000.parquet")
    if write:
        names = ["label"] + dense_names(sz) + sparse_names(sz)
        columns = [label] + list(dense.T) + list(sparse.T)
        table = pa.Table.from_arrays([pa.array(c) for c in columns],
                                     names=names)
        os.makedirs(path, exist_ok=True)
        pq.write_table(table, file, row_group_size=sz["rows_per_row_group"],
                       compression="snappy")
    nbytes = os.path.getsize(file)
    return Data("file://" + os.path.abspath(path), label, dense, sparse,
                nbytes)


def make_reader(data, sz, seed, **kwargs):
    from petastorm_tpu import make_batch_reader

    return make_batch_reader(
        data.url, reader_pool_type=sz["reader_pool"],
        workers_count=sz["reader_workers"], shuffle_row_groups=True,
        shard_seed=seed, num_epochs=None, **kwargs)


def device_stage(sz, seed):
    return None


# -- consumer step ---------------------------------------------------------

def init_params(sz, key):
    """DLRM parameters from ``key``: the arithmetic of
    ``models/tabular_dlrm.py::init_dlrm_params``, kept here so the weights
    are the benchmark's and the reference rebuilds them alike."""
    import jax
    import jax.numpy as jnp

    nd, ns, d = sz["num_dense"], sz["num_sparse"], sz["embed_dim"]
    bh, th = sz["bottom_mlp_widths"][0], sz["top_mlp_widths"][0]
    vocab = sz["num_embeddings_per_feature"]
    k_emb, k_b1, k_b2, k_t1, k_t2 = jax.random.split(key, 5)
    f = ns + 1
    interact = f * (f - 1) // 2 + d

    def dense(k, i, o):
        return {"kernel": jax.random.normal(k, (i, o), jnp.float32)
                * (1.0 / jnp.sqrt(float(i))),
                "bias": jnp.zeros((o,), jnp.float32)}

    return {
        "embeddings": jax.random.normal(k_emb, (ns, vocab, d),
                                        jnp.float32) * 0.05,
        "bottom1": dense(k_b1, nd, bh),
        "bottom2": dense(k_b2, bh, d),
        "top1": dense(k_t1, interact, th),
        "top2": dense(k_t2, th, 1),
    }


def _columns(batch, sz):
    import jax.numpy as jnp

    dense = jnp.stack([batch[k] for k in dense_names(sz)], axis=1)
    sparse = jnp.stack([batch[k] for k in sparse_names(sz)], axis=1)
    return dense, sparse, batch["label"]


def make_step(sz):
    """``step(params, batch) -> (params, loss)``: the program's DLRM SGD
    step on the loader's 40 column arrays, stacked inside the step."""
    import jax.numpy as jnp

    from petastorm_tpu.models.tabular_dlrm import make_dlrm_train_step

    train = make_dlrm_train_step(sz["learning_rate"])

    def step(params, batch):
        dense, sparse, label = _columns(batch, sz)
        return train(params, dense, sparse, label,
                     jnp.ones(label.shape, bool))

    return step


def step_flops(sz, batch):
    """Forward + backward operations of one step: the MLPs' matmuls and the
    pairwise interaction (3x forward: no input gradient is needed for the
    dense features, but the interaction's inputs all need one, so only the
    first bottom layer saves a third). The embedding gather and the dense
    table update move bytes, not operations, and are left out."""
    nd, ns, d = sz["num_dense"], sz["num_sparse"], sz["embed_dim"]
    bh, th = sz["bottom_mlp_widths"][0], sz["top_mlp_widths"][0]
    f = ns + 1
    interact = f * (f - 1) // 2 + d
    bottom1 = 2 * nd * bh
    rest = 2 * bh * d + 2 * f * f * d + 2 * interact * th + 2 * th
    return batch * (2 * bottom1 + 3 * rest)


def input_bytes(sz, batch):
    """No device-side input work: the columns are staged as they are."""
    return None


# -- what the window keeps for the check -----------------------------------

class Record:
    """Keeps every delivered batch's label column (it names the batch), and
    every column of the warm-up batches and of ``SAMPLES`` window batches
    drawn from the seed."""

    def __init__(self, sz, seed, warm):
        rng = np.random.default_rng([seed, 23])
        self.sample = set(range(warm)) | set(
            int(k) for k in warm + rng.choice(SAMPLE_RANGE, SAMPLES,
                                              replace=False))
        self.sz = sz
        self.labels, self.batches = [], {}

    def keep(self, k, batch):
        self.labels.append(batch["label"])
        if k in self.sample:
            self.batches[k] = dict(batch)

    def to_host(self):
        import jax

        sz = self.sz
        self.labels = [np.asarray(a) for a in jax.device_get(self.labels)]
        host = jax.device_get(self.batches)
        self.batches = {
            k: (np.asarray(v["label"]),
                np.stack([np.asarray(v[n]) for n in dense_names(sz)], axis=1),
                np.stack([np.asarray(v[n]) for n in sparse_names(sz)], axis=1))
            for k, v in host.items()}


# -- the plain reference ---------------------------------------------------

def batch_starts(data, record, sz):
    """The first row of every delivered batch, found by its label column
    (batches are aligned slices of row groups); -1 where none matches."""
    b = sz["batch_per_chip"]
    index = {data.label[s:s + b].tobytes(): s
             for s in range(0, sz["rows"] - b + 1, b)}
    return [index.get(lab.tobytes(), -1) for lab in record.labels]


def check_rows(data, record, sz, seed, ref_order):
    """Numbers for the rows and their order (``ref_order(seed, groups,
    epochs)``, the delivery path's), and every value of the compared
    batches against the rows as written (exact)."""
    from harness.compare import order_readings

    b, group = sz["batch_per_chip"], sz["rows_per_row_group"]
    starts = batch_starts(data, record, sz)
    unknown = sum(s < 0 for s in starts)
    per_group = group // b
    # A row group arrives as ``per_group`` consecutive aligned batches.
    groups, broken = [], 0
    for i in range(0, len(starts) - per_group + 1, per_group):
        chunk = starts[i:i + per_group]
        g = chunk[0] // group
        if chunk != [g * group + j * b for j in range(per_group)]:
            broken += 1
        groups.append(g)
    epochs = -(-len(groups) * group // sz["rows"]) + 1
    mismatched = 0
    for k, (label, dense, sparse) in record.batches.items():
        s = starts[k]
        if s < 0:
            mismatched += b
            continue
        want = (data.label[s:s + b], data.dense[s:s + b],
                data.sparse[s:s + b].astype(sparse.dtype))
        bad = (label != want[0]) | np.any(dense != want[1], axis=1) \
            | np.any(sparse != want[2], axis=1)
        mismatched += int(bad.sum())
    return {"batches_not_row_groups": unknown + broken,
            **order_readings(groups, ref_order(
                seed, sz["rows"] // group, epochs), sz["rows"] // group),
            "rows_mismatched": mismatched}


def _ref_forward(params, dense, sparse, q):
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST

    def lin(x, p, relu=True):
        y = jnp.dot(q(x), q(p["kernel"]), precision=hi) + p["bias"]
        return jax.nn.relu(y) if relu else y

    emb = params["embeddings"]
    vocab = emb.shape[1]
    x = lin(lin(dense, params["bottom1"]), params["bottom2"])
    ids = (sparse % vocab).astype(jnp.int32)
    looked = jnp.stack([q(emb[t])[ids[:, t]] for t in range(emb.shape[0])],
                       axis=1)
    feats = jnp.concatenate([x[:, None], looked], axis=1)       # [B, F, D]
    inter = jnp.einsum("bfd,bgd->bfg", q(feats), q(feats), precision=hi)
    iu, ju = np.triu_indices(feats.shape[1], k=1)
    top_in = jnp.concatenate([x, inter[:, iu, ju]], axis=1)
    return lin(lin(top_in, params["top1"]), params["top2"], relu=False)[:, 0]


def ref_step_fn(sz, quantize):
    """One float32 SGD step of the DLRM on binary cross-entropy:
    ``(params, (dense, sparse), labels) -> (params, loss, grad norms)``."""
    import jax
    import jax.numpy as jnp

    lr = sz["learning_rate"]
    q = quantize or (lambda x: x)

    def loss_fn(params, dense, sparse, labels):
        z = _ref_forward(params, dense, sparse, q)
        y = labels.astype(jnp.float32)
        return jnp.mean(jnp.maximum(z, 0) - z * y + jnp.log1p(jnp.exp(-jnp.abs(z))))

    def step(params, inputs, labels):
        dense, sparse = inputs
        loss, grads = jax.value_and_grad(loss_fn)(params, dense, sparse, labels)
        norms = jax.tree_util.tree_map(lambda g: jnp.sqrt(jnp.sum(g * g)), grads)
        return (jax.tree_util.tree_map(lambda p, g: p - lr * g, params, grads),
                loss, norms)

    return step


def ref_inputs(data, record, sz, seed, steps, quantize=None):
    """The reference's own rows for the first ``steps`` steps, by where the
    delivered batches start."""
    b = sz["batch_per_chip"]
    starts = batch_starts(data, record, sz)
    for k in range(steps):
        s = max(starts[k], 0)
        dense = data.dense[s:s + b]
        if quantize is not None:
            dense = np.asarray(quantize(dense))
        yield (dense, data.sparse[s:s + b].astype(np.int32)), data.label[s:s + b]


# -- limits and the control --------------------------------------------------

#: Each compared number's limit, set between the largest reading of sound
#: runs over a dozen seeds and more and the smallest of the control or of a
#: planted fault, on the chip at the cell's size: readings in PERF.md.
LIMITS = {
    "batches_not_row_groups": 0,
    "order_mean_lag": 8.0,
    "rows_once_violations": 0,
    "rows_mismatched": 0,
    "loss_gap": 0.0045,
    "grad1_gap": 0.1,
    "change3_gap": 0.1,
}


def control_quantize(x):
    """The control's precision: float8 e4m3 with one scale per tensor."""
    from harness.precision import fp8

    return fp8(x)


def control_rows(data, record, sz, seed, ref_order):
    """The control's rows: the dense features as float8 would carry them;
    and the order numbers of the reference's row groups with another
    seed's order or with a row group delivered twice in the program's
    place."""
    b = sz["batch_per_chip"]
    starts = batch_starts(data, record, sz)
    bad = 0
    for k in record.batches:
        dense = data.dense[starts[k]:starts[k] + b]
        bad += int(np.sum(np.any(np.asarray(control_quantize(dense)) != dense,
                                 axis=1)))
    from harness.compare import order_faults

    groups = sz["rows"] // sz["rows_per_row_group"]
    n = len(record.labels) * sz["batch_per_chip"] // sz["rows_per_row_group"]
    epochs = -(-n * sz["rows_per_row_group"] // sz["rows"]) + 1
    return {"rows_mismatched": bad,
            **order_faults(ref_order(seed, groups, epochs),
                           ref_order(seed + 1, groups, epochs), n, groups)}
