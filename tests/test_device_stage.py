"""Device decode stage: CPU-backend parity, seeding, delivery, accounting.

The decode-ceiling contract (docs/guides/device_decode.md): the fused
on-device cast/normalize must match the host decode path BIT-EXACTLY on
the CPU backend (crop/flip are exact index selections), the seeded augment
stream must be reproducible across runs and invariant to prefetch depth
and staging-thread placement, sharded delivery must land each shard on its
target device, and the H2D ledger must count uint8 bytes, not float32
pixels. Runs on the conftest 8-virtual-device CPU mesh.
"""

import functools

import numpy as np
import pytest

from petastorm_tpu.jax_utils import (
    DeviceStage,
    JaxDataLoader,
    batch_iterator,
    batch_sharding,
    make_jax_dataloader,
)
from petastorm_tpu.schema.codecs import ScalarCodec
from petastorm_tpu.schema.unischema import Unischema, UnischemaField
from petastorm_tpu.test_util.reader_mock import ReaderMock

IMG_SHAPE = (16, 12, 3)

ImageSchema = Unischema("ImageSchema", [
    UnischemaField("id", np.int64, (), ScalarCodec(), False),
    UnischemaField("image", np.uint8, IMG_SHAPE, None, False),
    UnischemaField("weight", np.float32, (), None, False),
])


def _row(i):
    rng = np.random.RandomState(i)
    return {"id": np.int64(i),
            "image": rng.randint(0, 256, IMG_SHAPE, dtype=np.uint8),
            "weight": np.float32(i) / 7.0}


def _reader(rows=16):
    return ReaderMock(ImageSchema, _row, num_rows=rows)


def _raw_batches(rows=16, batch=8):
    return list(batch_iterator(_reader(rows), batch, last_batch="drop"))


# --- field routing --------------------------------------------------------


def test_split_infers_uint8_image_fields():
    stage = DeviceStage()
    batch = _raw_batches()[0]
    raw, rest = stage.split(batch)
    assert set(raw) == {"image"}
    assert set(rest) == {"id", "weight"}


def test_split_explicit_fields_and_missing_field_error():
    stage = DeviceStage(image_fields=("image",))
    raw, _ = stage.split(_raw_batches()[0])
    assert set(raw) == {"image"}
    with pytest.raises(KeyError, match="absent"):
        DeviceStage(image_fields=("nope",)).split(_raw_batches()[0])


def test_split_names_dtype_problem_for_object_columns():
    """An explicitly named field that collated to object dtype must raise a
    dtype error, not claim the field is absent while listing it present."""
    batch = dict(_raw_batches()[0])
    ragged = np.empty(8, dtype=object)
    for i in range(8):
        ragged[i] = np.zeros((i + 1, 3), np.uint8)  # per-row shapes differ
    batch["image"] = ragged
    with pytest.raises(TypeError, match="object dtype"):
        DeviceStage(image_fields=("image",)).split(batch)


def test_stage_validates_bad_configs():
    with pytest.raises(ValueError, match="non-zero"):
        DeviceStage(normalize=(0.0, 0.0))
    with pytest.raises(ValueError, match="positive"):
        DeviceStage(crop=(0, 4))
    with pytest.raises(ValueError, match="scalars or 1-D"):
        DeviceStage(normalize=(np.zeros((2, 2)), 1.0))


# --- kernel vs host reference (the CPU-backend parity contract) -----------


def test_cast_normalize_bit_exact_vs_host_reference():
    stage = DeviceStage(normalize=((10.0, 20.0, 30.0), (2.0, 4.0, 8.0)))
    raw = {"image": _raw_batches()[0]["image"]}
    got = stage.apply({"image": raw["image"]}, 0)
    want = stage.host_reference(raw, 0)
    assert np.asarray(got["image"]).dtype == np.float32
    # Bit-exact: same cast, same precomputed reciprocal, same op order.
    np.testing.assert_array_equal(np.asarray(got["image"]), want["image"])


def test_cast_normalize_matches_plain_numpy_arithmetic():
    mean, std = 127.5, 63.75
    stage = DeviceStage(normalize=(mean, std))
    img = _raw_batches()[0]["image"]
    got = np.asarray(stage.apply({"image": img}, 3)["image"])
    want = (img.astype(np.float32) - np.float32(mean)) \
        * (np.float32(1.0) / np.float32(std))
    np.testing.assert_array_equal(got, want)


_CROP_SHAPES = {
    "whole": ((3, 5, 7, 3), (5, 7)),          # the crop is the whole image
    "1x1": ((2, 9, 9, 3), (1, 1)),
    "h_ne_w": ((8, 16, 12, 3), (8, 6)),       # H != W, image and crop
    "imagenet": ((4, 375, 500, 3), (224, 224)),  # ImageNet's commonest size
}
_CROP_CASES = [
    pytest.param(shape, flip, out, "uint8",
                 id=f"{shape}-{'flip' if flip else 'noflip'}-{out}")
    for shape in _CROP_SHAPES for flip in (False, True)
    for out in ("float32", "bfloat16")
] + [
    # Input dtypes: bf16 holds int8 and bool exactly; uint16 above 256 and
    # float32 (with NaN and Inf) it would round or spread.
    pytest.param("h_ne_w", True, "float32", src,
                 id=f"h_ne_w-flip-float32-from-{src}")
    for src in ("int8", "bool", "uint16", "float32")
]


def _crop_input(shape, src):
    rng = np.random.RandomState(sum(shape))
    if src == "uint8":
        img = rng.randint(0, 256, shape, dtype=np.uint8)
        img[0, 0, 0] = (0, 255, 128)  # both ends of the uint8 range
    elif src == "int8":
        img = rng.randint(-128, 128, shape).astype(np.int8)
        img[0, 0, 0] = (-128, 127, 0)
    elif src == "bool":
        img = rng.randint(0, 2, shape).astype(bool)
    elif src == "uint16":
        img = rng.randint(0, 1 << 16, shape).astype(np.uint16)
        img[:, :, :, 0] = 257  # bf16 would read 256
    else:
        img = rng.uniform(-1e6, 1e6, shape).astype(np.float32)
        img[:, ::3, ::2, 0] = np.nan  # in every crop, not in every row
        img[:, 1::3, ::2, 1] = np.inf
    return img


@pytest.mark.parametrize("path", ["apply", "select"])
@pytest.mark.parametrize("shape,flip,out,src", _CROP_CASES)
def test_crop_flip_exact_selections_match_host_reference(shape, flip, out,
                                                         src, path):
    """Both device crops, the per-image slice that ``apply`` takes here and
    the one-hot selection a TPU takes for a batch in its tiles (forced),
    agree bit for bit with the numpy reference's slice, flipped or not, in
    either output dtype; a batch that bf16 cannot hold is sliced even when
    the selection is asked for."""
    import jax
    import ml_dtypes

    shape, crop = _CROP_SHAPES[shape]
    out_dtype = ml_dtypes.bfloat16 if out == "bfloat16" else np.float32
    # Seed 1 at step 7 draws flipped and unflipped images in every case.
    stage = DeviceStage(image_fields=("image",), crop=crop, flip=flip,
                        seed=1, output_dtype=out_dtype,
                        normalize=((123.675, 116.28, 103.53),
                                   (58.395, 57.12, 57.375)))
    img = _crop_input(shape, src)
    if path == "apply":
        got = stage.apply({"image": img}, 7)["image"]
    else:
        kernel = functools.partial(stage._kernel, select=frozenset({"image"}))
        got = jax.jit(kernel)({"image": img}, np.int32(7))["image"]
    got = np.asarray(got)
    want = stage.host_reference({"image": img}, 7)["image"]
    assert got.shape == (shape[0],) + crop + (3,)
    assert got.dtype == want.dtype == out_dtype
    if src == "float32":
        # NaN compares equal to NaN here, and stays where it was.
        assert np.isnan(want).any() and np.isfinite(want).any()
        np.testing.assert_array_equal(got, want)
    else:
        np.testing.assert_array_equal(got.view(np.uint8),
                                      want.view(np.uint8))


def test_crop_path_follows_the_device_layout():
    """Only a layout with the batch in its two minor dimensions takes the
    selection: a CPU array's layout is row-major, and a numpy batch has
    none. The TPU's layouts are checked in test_tpu_compile.py."""
    import jax

    from petastorm_tpu.jax_utils.device_stage import batch_in_tiles

    staged = jax.device_put(_raw_batches()[0]["image"])
    assert not batch_in_tiles(staged.format.layout)
    assert not batch_in_tiles(None)


def test_crop_actually_varies_per_image_and_flip_flips():
    # With a 16x12 image and an 8x6 crop there are 63 possible offsets per
    # image; 8 images sharing one offset (or no flip bit set) would make
    # the augment a no-op — catch a PRNG wiring bug, not randomness.
    stage = DeviceStage(crop=(8, 6), flip=True, seed=0)
    img = _raw_batches()[0]["image"]
    out1 = np.asarray(stage.apply({"image": img}, 0)["image"])
    out2 = np.asarray(stage.apply({"image": img}, 1)["image"])
    assert out1.shape == out2.shape == (8, 8, 6, 3)
    assert not np.array_equal(out1, out2), \
        "different steps must draw different augments"


def test_bfloat16_output_dtype():
    import ml_dtypes

    stage = DeviceStage(output_dtype=ml_dtypes.bfloat16,
                        normalize=(127.5, 127.5))
    got = stage.apply({"image": _raw_batches()[0]["image"]}, 0)
    assert np.asarray(got["image"]).dtype == ml_dtypes.bfloat16


def test_seed_determinism_across_instances():
    img = _raw_batches()[0]["image"]
    a = DeviceStage(crop=(8, 6), flip=True, seed=9)
    b = DeviceStage(crop=(8, 6), flip=True, seed=9)
    c = DeviceStage(crop=(8, 6), flip=True, seed=10)
    out_a = np.asarray(a.apply({"image": img}, 4)["image"])
    out_b = np.asarray(b.apply({"image": img}, 4)["image"])
    out_c = np.asarray(c.apply({"image": img}, 4)["image"])
    np.testing.assert_array_equal(out_a, out_b)
    assert not np.array_equal(out_a, out_c)


# --- loader integration ---------------------------------------------------


def _loader_outputs(**kwargs):
    stage = DeviceStage(normalize=(127.5, 127.5), crop=(8, 6), flip=True,
                        seed=21)
    loader = make_jax_dataloader(_reader(), 8, device_stage=stage,
                                 **kwargs)
    with loader:
        return [np.asarray(b["image"]) for b in loader], loader


def test_loader_device_stage_end_to_end_matches_host_path():
    import jax

    stage = DeviceStage(normalize=(127.5, 127.5), seed=2)
    loader = make_jax_dataloader(_reader(), 8, device_stage=stage)
    with loader:
        batches = list(loader)
    assert len(batches) == 2
    assert isinstance(batches[0]["image"], jax.Array)
    assert batches[0]["image"].dtype == np.float32
    # Non-image numeric fields still stage; strings would passthrough.
    assert isinstance(batches[0]["id"], jax.Array)
    # The host decode path (reference): identical collation, host arithmetic.
    ref_stage = DeviceStage(normalize=(127.5, 127.5), seed=2)
    for step, (got, raw) in enumerate(zip(batches, _raw_batches())):
        want = ref_stage.host_reference({"image": raw["image"]}, step)
        np.testing.assert_array_equal(np.asarray(got["image"]),
                                      want["image"])


def test_augment_reproducible_across_runs_and_prefetch_depths():
    out1, _ = _loader_outputs(device_prefetch=1, host_prefetch=1)
    out2, _ = _loader_outputs(device_prefetch=4, host_prefetch=6)
    out3, _ = _loader_outputs(stage_in_producer=True, device_prefetch=3)
    assert len(out1) == len(out2) == len(out3) == 2
    for a, b, c in zip(out1, out2, out3):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(a, c)


def test_augment_advances_across_iterations_reproducibly():
    """Epoch 2 must draw FRESH augments (the step ordinal is monotonic
    across the SAME loader's iterations), and a fresh identically-
    configured loader must reproduce both epochs — the
    reproducible-training contract."""
    def two_epochs():
        reader = _reader()
        stage = DeviceStage(crop=(8, 6), flip=True, seed=33)
        loader = make_jax_dataloader(reader, 8, device_stage=stage)
        epochs = []
        with loader:
            for _ in range(2):
                epochs.append([np.asarray(b["image"]) for b in loader])
                reader.reset()
        return epochs

    run1, run2 = two_epochs(), two_epochs()
    for e1, e2 in zip(run1, run2):
        for a, b in zip(e1, e2):
            np.testing.assert_array_equal(a, b)
    assert not np.array_equal(run1[0][0], run1[1][0]), \
        "epoch 2 must not replay epoch 1's augments"


def test_device_stage_rejects_host_only_loader():
    with pytest.raises(ValueError, match="stage_to_device"):
        make_jax_dataloader(_reader(), 8, device_stage=DeviceStage(),
                            stage_to_device=False)


def test_h2d_bytes_counts_raw_uint8_not_float32():
    stage = DeviceStage(normalize=(127.5, 127.5))
    loader = make_jax_dataloader(_reader(), 8, device_stage=stage,
                                 non_tensor_policy="drop")
    with loader:
        batches = list(loader)
    rows = 8 * len(batches)
    diag = loader.diagnostics
    img_bytes = rows * int(np.prod(IMG_SHAPE))          # uint8: 1 B/px
    other_bytes = rows * (8 + 4)                        # id int64 + weight f32
    assert diag["h2d_bytes"] == img_bytes + other_bytes
    assert stage.h2d_bytes == img_bytes
    # The float32 pixels the device decoded into were never staged: the
    # ledger is 1/4 of a float32-staging pipeline's image bytes.
    assert diag["h2d_bytes"] < rows * int(np.prod(IMG_SHAPE)) * 4


def test_device_stage_diagnostics_and_overlap_gauge():
    _, loader = _loader_outputs()
    diag = loader.diagnostics
    assert diag["raw_stage_s"] > 0
    assert diag["device_decode_s"] > 0
    assert diag["device_dispatch_s"] >= (diag["raw_stage_s"]
                                         + diag["device_decode_s"])
    assert 0.0 <= diag["dispatch_overlap_pct"] <= 100.0
    # The gauge mirrors the derived value for scrapers.
    assert loader._m_overlap.value == diag["dispatch_overlap_pct"]


# --- sharded direct-to-device delivery ------------------------------------


def test_sharded_device_stage_delivers_global_arrays():
    import jax
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:8]).reshape(8), ("data",))
    sharding = batch_sharding(mesh, "data")
    stage = DeviceStage(normalize=(127.5, 127.5), seed=4)
    loader = make_jax_dataloader(_reader(), 8, sharding=sharding,
                                 device_stage=stage,
                                 non_tensor_policy="drop")
    with loader:
        batches = list(loader)
    ref_stage = DeviceStage(normalize=(127.5, 127.5), seed=4)
    for step, (got, raw) in enumerate(zip(batches, _raw_batches())):
        arr = got["image"]
        assert isinstance(arr, jax.Array)
        assert arr.sharding.is_equivalent_to(sharding, arr.ndim)
        assert len(arr.addressable_shards) == 8
        want = ref_stage.host_reference({"image": raw["image"]}, step)
        np.testing.assert_array_equal(np.asarray(arr), want["image"])
    # Per-shard puts were observed: at least one timed put per target
    # device per batch for the raw image field (numeric fields shard too).
    assert loader.diagnostics["shard_put_s"] >= 0.0
    assert loader._m_stage["shard_put"].count >= 8 * len(batches)
    # a pjit-style consumer takes the global array without resharding
    total = jax.jit(lambda x: x.sum())(batches[0]["image"])
    np.testing.assert_allclose(
        float(total), float(np.asarray(batches[0]["image"]).sum()),
        rtol=1e-5)


def test_direct_shard_put_matches_process_local_fallback():
    import jax
    from jax.sharding import Mesh

    from petastorm_tpu.jax_utils.sharding import local_data_to_global_array

    mesh = Mesh(np.array(jax.devices()[:8]).reshape(8), ("data",))
    sharding = batch_sharding(mesh, "data")
    arr = np.arange(8 * 6, dtype=np.float32).reshape(8, 6)
    observed = []
    direct = local_data_to_global_array(sharding, arr,
                                        observe_shard_put=observed.append)
    fallback = jax.make_array_from_process_local_data(sharding, arr)
    assert len(observed) == 8          # one timed put per target device
    assert direct.sharding.is_equivalent_to(sharding, direct.ndim)
    np.testing.assert_array_equal(np.asarray(direct), np.asarray(fallback))
    for shard, want in zip(
            sorted(direct.addressable_shards,
                   key=lambda s: s.index[0].start or 0),
            np.split(arr, 8)):
        np.testing.assert_array_equal(np.asarray(shard.data), want)


def test_batch_source_device_stage_pipeline():
    """The scaling leg's shape: raw in-memory batches through batch_source
    + sharding + device stage."""
    import jax
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:8]).reshape(8), ("data",))
    sharding = batch_sharding(mesh, "data")
    rng = np.random.RandomState(0)
    images = rng.randint(0, 255, (16,) + IMG_SHAPE, dtype=np.uint8)

    def source():
        return iter([{"image": images}] * 3)

    stage = DeviceStage(normalize=(127.5, 127.5))
    loader = JaxDataLoader(None, 16, batch_source=source, sharding=sharding,
                           device_stage=stage, max_batches=3,
                           non_tensor_policy="drop")
    with loader:
        batches = list(loader)
    assert len(batches) == 3
    assert batches[0]["image"].shape == (16,) + IMG_SHAPE
    assert len(batches[0]["image"].addressable_shards) == 8
