"""AOT compiles of the main path's programs for a described v5e chip.

Nothing runs: the TPU compiler refuses here what the chip would refuse
(unaligned tiles, too much VMEM, a program over the device's memory) at no
chip time. The topology is described only inside the module fixture below —
never at import — because one process at a time may load the TPU library
(``on-chip-measurement`` guide, section 2); keep every such compile in this
one file. The persistent compile cache is off around them: an entry written
without a chip cannot be read back.
"""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

V5E_HBM_BYTES = 16 * 10 ** 9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    prev_log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev_cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev_cache)
    compilation_cache.reset_cache()
    if prev_log_dir is None:
        os.environ.pop("TPU_LOG_DIR", None)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_flash_fwd_bwd(q, k, v, segment_ids=None):
    from petastorm_tpu.ops.flash_attention import flash_attention

    def loss(q, k, v, segment_ids):
        out = flash_attention(q, k, v, interpret=False, causal=True,
                              segment_ids=segment_ids)
        return jnp.sum(out.astype(jnp.float32))

    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        q, k, v, segment_ids).compile()


def test_flash_causal_long_bf16(one_chip):
    qkv = [_spec((1, 8192, 8, 128), jnp.bfloat16, one_chip)] * 3
    assert "tpu_custom_call" in _compile_flash_fwd_bwd(*qkv).as_text()


def test_flash_gqa_segment_ids(one_chip):
    b, t, d = 1, 4096, 128
    q = _spec((b, t, 32, d), jnp.bfloat16, one_chip)
    kv = _spec((b, t, 8, d), jnp.bfloat16, one_chip)
    segs = _spec((b, t), jnp.int32, one_chip)
    compiled = _compile_flash_fwd_bwd(q, kv, kv, segs)
    assert "tpu_custom_call" in compiled.as_text()


def test_device_stage_imagenet_batch(topo, one_chip):
    from petastorm_tpu.jax_utils import DeviceStage

    stage = DeviceStage(image_fields=("image",), crop=(224, 224), flip=True,
                        normalize=((123.675, 116.28, 103.53),
                                   (58.395, 57.12, 57.375)),
                        output_dtype=jnp.bfloat16)
    raw = {"image": _spec((128, 375, 500, 3), jnp.uint8, one_chip)}
    step = _spec((), jnp.int32, one_chip)
    compiled = jax.jit(stage._kernel).lower(raw, step).compile()
    assert compiled.output_shardings["image"].device_set \
        == {topo.devices[0]}
    out = jax.eval_shape(stage._kernel, raw, step)["image"]
    assert (out.shape, out.dtype) == ((128, 224, 224, 3), jnp.bfloat16)


def test_classifier_step_fits_one_chip(one_chip):
    """The donated train step at chip_smoke.py's width (1.65 B params)."""
    from petastorm_tpu.models.image_classifier import (init_params,
                                                       make_train_step)

    init = functools.partial(init_params, image_shape=(224, 224, 3),
                             num_classes=1000, hidden=2048, conv_features=64)
    params = jax.tree_util.tree_map(
        lambda x: _spec(x.shape, x.dtype, one_chip),
        jax.eval_shape(init, jax.random.PRNGKey(0)))
    compiled = jax.jit(make_train_step(), donate_argnums=(0,)).lower(
        params, _spec((128, 224, 224, 3), jnp.bfloat16, one_chip),
        _spec((128,), jnp.int32, one_chip),
        _spec((128,), jnp.bool_, one_chip)).compile()
    mem = compiled.memory_analysis()
    n_params = sum(int(np.prod(x.shape))
                   for x in jax.tree_util.tree_leaves(params))
    assert n_params > 1.6e9
    # Donated parameters alias the updated ones: arguments + temporaries
    # is the program's footprint.
    assert mem.alias_size_in_bytes >= 4 * n_params
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < V5E_HBM_BYTES
