"""Pallas flash-attention numerics vs the dense reference (interpret mode on
the CPU test backend; Mosaic lowering exercises on real TPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from petastorm_tpu.models.sequence_model import attention_reference
from petastorm_tpu.ops import flash_attention


def _qkv(b=2, t=48, h=2, d=16, seed=0, dtype=np.float32):
    rng = np.random.RandomState(seed)
    return tuple(jnp.asarray(rng.randn(b, t, h, d).astype(dtype))
                 for _ in range(3))


def test_matches_reference_single_block():
    q, k, v = _qkv(t=16)
    out = flash_attention(q, k, v, block_q=16, block_k=16)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(attention_reference(q, k, v)),
                               rtol=1e-5, atol=1e-5)


def test_matches_reference_multi_block_online_softmax():
    q, k, v = _qkv(t=64)
    # 4 K blocks: the online max/sum rescaling path is exercised.
    out = flash_attention(q, k, v, block_q=16, block_k=16)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(attention_reference(q, k, v)),
                               rtol=1e-5, atol=1e-5)


def test_ragged_lengths_are_padded_and_masked():
    q, k, v = _qkv(t=50)  # not a multiple of the block
    out = flash_attention(q, k, v, block_q=16, block_k=16)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(attention_reference(q, k, v)),
                               rtol=1e-5, atol=1e-5)


def test_cross_attention_lengths():
    rng = np.random.RandomState(3)
    q = jnp.asarray(rng.randn(1, 24, 2, 8).astype(np.float32))
    k = jnp.asarray(rng.randn(1, 40, 2, 8).astype(np.float32))
    v = jnp.asarray(rng.randn(1, 40, 2, 8).astype(np.float32))
    out = flash_attention(q, k, v, block_q=8, block_k=16)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(attention_reference(q, k, v)),
                               rtol=1e-5, atol=1e-5)


def test_bfloat16_inputs():
    q, k, v = _qkv(t=32, dtype=np.float32)
    q, k, v = (x.astype(jnp.bfloat16) for x in (q, k, v))
    out = flash_attention(q, k, v, block_q=16, block_k=16)
    assert out.dtype == jnp.bfloat16
    ref = attention_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_gradients_flow_and_match_reference():
    q, k, v = _qkv(t=32, d=8)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, 16, 16) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(attention_reference(q, k, v) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_causal_matches_masked_reference():
    from petastorm_tpu.ops.flash_attention import _attention_reference

    q, k, v = _qkv(t=48, seed=8)
    out = flash_attention(q, k, v, block_q=16, block_k=16, causal=True)
    ref = _attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    # Causal must differ from full attention (sanity that the mask bites).
    full = flash_attention(q, k, v, block_q=16, block_k=16)
    assert not np.allclose(np.asarray(out), np.asarray(full))


def test_causal_cross_lengths_suffix_alignment():
    from petastorm_tpu.ops.flash_attention import _attention_reference

    rng = np.random.RandomState(9)
    q = jnp.asarray(rng.randn(1, 8, 2, 8).astype(np.float32))   # suffix
    k = jnp.asarray(rng.randn(1, 32, 2, 8).astype(np.float32))
    v = jnp.asarray(rng.randn(1, 32, 2, 8).astype(np.float32))
    out = flash_attention(q, k, v, block_q=8, block_k=16, causal=True)
    ref = _attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_causal_more_queries_than_keys_is_nan_free():
    from petastorm_tpu.ops.flash_attention import _attention_reference

    rng = np.random.RandomState(11)
    q = jnp.asarray(rng.randn(1, 16, 2, 8).astype(np.float32))
    k = jnp.asarray(rng.randn(1, 8, 2, 8).astype(np.float32))
    v = jnp.asarray(rng.randn(1, 8, 2, 8).astype(np.float32))
    # Suffix alignment: the first 8 query rows precede every key -> fully
    # masked -> must be exactly zero, nan-free, in forward AND backward,
    # and kernel and oracle must agree.
    out = flash_attention(q, k, v, block_q=8, block_k=8, causal=True)
    ref = _attention_reference(q, k, v, causal=True)
    assert np.isfinite(np.asarray(out)).all()
    assert np.isfinite(np.asarray(ref)).all()
    np.testing.assert_allclose(np.asarray(out[:, :8]), 0.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    grads = jax.grad(lambda a, b, c: jnp.sum(
        flash_attention(a, b, c, 8, 8, None, True) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for g in grads:
        assert np.isfinite(np.asarray(g)).all()


def test_causal_gradients_match_reference():
    from petastorm_tpu.ops.flash_attention import _attention_reference

    q, k, v = _qkv(t=32, d=8, seed=10)
    g_flash = jax.grad(lambda a, b, c: jnp.sum(
        flash_attention(a, b, c, 16, 16, None, True) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(lambda a, b, c: jnp.sum(
        _attention_reference(a, b, c, causal=True).astype(jnp.float32) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_seq_model_flash_path_matches_dense():
    from petastorm_tpu.models.sequence_model import (apply_seq_model,
                                                     init_seq_params)

    params = init_seq_params(jax.random.PRNGKey(0), feature_dim=6,
                             d_model=32, num_heads=4)
    windows = np.random.RandomState(5).randn(4, 24, 6).astype(np.float32)
    dense = apply_seq_model(params, jnp.asarray(windows), num_heads=4,
                            compute_dtype=jnp.float32)
    flash = apply_seq_model(params, jnp.asarray(windows), num_heads=4,
                            compute_dtype=jnp.float32, attn_impl="flash")
    np.testing.assert_allclose(np.asarray(flash), np.asarray(dense),
                               rtol=1e-4, atol=1e-4)


def test_jit_composes():
    q, k, v = _qkv(t=32)
    f = jax.jit(lambda a, b, c: flash_attention(a, b, c, 16, 16))
    np.testing.assert_allclose(np.asarray(f(q, k, v)),
                               np.asarray(attention_reference(q, k, v)),
                               rtol=1e-5, atol=1e-5)


# --- hand-tiled flash backward (round 4) ----------------------------------

def _ref_grads(q, k, v, causal=False):
    from petastorm_tpu.ops.flash_attention import _attention_reference
    return jax.grad(lambda a, b, c: jnp.sum(
        _attention_reference(a, b, c, causal=causal).astype(jnp.float32)
        ** 2), argnums=(0, 1, 2))(q, k, v)


def _flash_grads(q, k, v, bq, bk, causal=False, bwd_impl="flash"):
    return jax.grad(lambda a, b, c: jnp.sum(
        flash_attention(a, b, c, bq, bk, None, causal, bwd_impl) ** 2),
        argnums=(0, 1, 2))(q, k, v)


def test_flash_bwd_ragged_lengths():
    q, k, v = _qkv(t=50, d=8, seed=21)  # t does not divide the block
    for a, b in zip(_flash_grads(q, k, v, 16, 16), _ref_grads(q, k, v)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_flash_bwd_causal_ragged():
    q, k, v = _qkv(t=50, d=8, seed=22)
    for a, b in zip(_flash_grads(q, k, v, 16, 16, causal=True),
                    _ref_grads(q, k, v, causal=True)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_flash_bwd_causal_cross_lengths():
    rng = np.random.RandomState(23)
    q = jnp.asarray(rng.randn(2, 24, 2, 8).astype(np.float32))
    k = jnp.asarray(rng.randn(2, 40, 2, 8).astype(np.float32))
    v = jnp.asarray(rng.randn(2, 40, 2, 8).astype(np.float32))
    for a, b in zip(_flash_grads(q, k, v, 8, 16, causal=True),
                    _ref_grads(q, k, v, causal=True)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_flash_bwd_causal_more_queries_than_keys():
    rng = np.random.RandomState(24)
    q = jnp.asarray(rng.randn(1, 16, 2, 8).astype(np.float32))
    k = jnp.asarray(rng.randn(1, 8, 2, 8).astype(np.float32))
    v = jnp.asarray(rng.randn(1, 8, 2, 8).astype(np.float32))
    flash = _flash_grads(q, k, v, 8, 8, causal=True)
    for g in flash:
        assert np.isfinite(np.asarray(g)).all()
    for a, b in zip(flash, _ref_grads(q, k, v, causal=True)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_flash_bwd_bfloat16():
    q, k, v = _qkv(t=32, d=8, seed=25)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    flash = _flash_grads(qb, kb, vb, 16, 16)
    ref = _ref_grads(q, k, v)
    for a, b in zip(flash, ref):
        assert a.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b), rtol=6e-2, atol=6e-2)


def test_flash_bwd_reference_oracle_path():
    q, k, v = _qkv(t=48, d=8, seed=26)
    flash = _flash_grads(q, k, v, 16, 16, causal=True, bwd_impl="flash")
    oracle = _flash_grads(q, k, v, 16, 16, causal=True,
                          bwd_impl="reference")
    for a, b in zip(flash, oracle):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_flash_bwd_under_jit():
    q, k, v = _qkv(t=32, d=8, seed=27)
    f = jax.jit(lambda a, b, c: jax.grad(
        lambda x, y, z: jnp.sum(flash_attention(x, y, z, 16, 16) ** 2),
        argnums=(0, 1, 2))(a, b, c))
    for a, b in zip(f(q, k, v), _ref_grads(q, k, v)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


# --- per-example kv_lengths (round 4) -------------------------------------

def _lens_oracle(q, k, v, lengths, causal=False):
    from petastorm_tpu.models.sequence_model import attention_reference
    return attention_reference(q, k, v, causal=causal, lengths=lengths)


def test_kv_lengths_forward_matches_oracle():
    q, k, v = _qkv(t=48, d=8, seed=30)
    lengths = jnp.asarray([48, 17, 33][:q.shape[0]], jnp.int32)
    out = flash_attention(q, k, v, 16, 16, kv_lengths=lengths)
    ref = _lens_oracle(q, k, v, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    # and it actually bites vs unmasked
    full = flash_attention(q, k, v, 16, 16)
    assert not np.allclose(np.asarray(out), np.asarray(full))


def test_kv_lengths_with_causal():
    q, k, v = _qkv(t=32, d=8, seed=31)
    lengths = jnp.asarray([32, 20], jnp.int32)
    out = flash_attention(q, k, v, 16, 16, causal=True, kv_lengths=lengths)
    ref = _lens_oracle(q, k, v, lengths, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_kv_lengths_backward_matches_oracle():
    q, k, v = _qkv(t=40, d=8, seed=32)
    lengths = jnp.asarray([40, 13], jnp.int32)

    def loss_flash(a, b, c):
        return jnp.sum(flash_attention(a, b, c, 16, 16,
                                       kv_lengths=lengths) ** 2)

    def loss_ref(a, b, c):
        return jnp.sum(_lens_oracle(a, b, c, lengths)
                       .astype(jnp.float32) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)
    # masked-out keys must receive exactly zero dk/dv
    np.testing.assert_array_equal(np.asarray(gf[1][1, 13:]), 0.0)
    np.testing.assert_array_equal(np.asarray(gf[2][1, 13:]), 0.0)


def test_kv_lengths_under_jit():
    q, k, v = _qkv(t=32, d=8, seed=33)
    lengths = jnp.asarray([10, 32], jnp.int32)
    f = jax.jit(lambda a, b, c, le: flash_attention(a, b, c, 16, 16,
                                                    kv_lengths=le))
    np.testing.assert_allclose(np.asarray(f(q, k, v, lengths)),
                               np.asarray(_lens_oracle(q, k, v, lengths)),
                               rtol=1e-5, atol=1e-5)


def test_with_lse_matches_dense_and_grads_flow_through_lse():
    """flash_attention_with_lse: the lse output equals the dense
    log-sum-exp (with -inf empty-set convention), and gradients flow
    through BOTH outputs (the backward's dlse term)."""
    from petastorm_tpu.ops.flash_attention import flash_attention_with_lse

    rng = np.random.RandomState(5)
    b, t, h, d = 2, 40, 2, 16
    q, k, v = (jnp.asarray(rng.randn(b, t, h, d).astype(np.float32))
               for _ in range(3))

    def dense(q, k, v, shift=0):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
        iq = jnp.arange(t)[:, None] + shift
        s = jnp.where((jnp.arange(t)[None, :] <= iq)[None, None], s,
                      -jnp.inf)
        lse = jax.scipy.special.logsumexp(s, axis=-1)
        safe = jnp.where(jnp.isneginf(lse), 0.0, lse)
        p = jnp.where(jnp.isneginf(lse)[..., None], 0.0,
                      jnp.exp(s - safe[..., None]))
        return (jnp.einsum("bhqk,bkhd->bqhd", p, v),
                lse.transpose(0, 2, 1))

    for shift in (0, -1):
        got_o, got_l = flash_attention_with_lse(
            q, k, v, block_q=16, block_k=16, causal=True,
            causal_shift=shift)
        want_o, want_l = dense(q, k, v, shift)
        np.testing.assert_allclose(np.asarray(got_o), np.asarray(want_o),
                                   rtol=1e-5, atol=1e-5)
        finite = np.isfinite(np.asarray(want_l))
        np.testing.assert_allclose(np.asarray(got_l)[finite],
                                   np.asarray(want_l)[finite],
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(np.isneginf(np.asarray(got_l)),
                                      ~finite)

    def loss_flash(q, k, v):
        o, l = flash_attention_with_lse(q, k, v, block_q=16, block_k=16,
                                        causal=True)
        return (o ** 2).sum() + (jnp.tanh(l) ** 2).sum()

    def loss_dense(q, k, v):
        o, l = dense(q, k, v)
        return (o ** 2).sum() + (jnp.tanh(l) ** 2).sum()

    gf = jax.grad(loss_flash, (0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, (0, 1, 2))(q, k, v)
    for a, b_, name in zip(gf, gd, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-4, atol=1e-4, err_msg=name)


def test_segment_id_pair_form_grads_through_public_api():
    """flash_attention accepts the (q_ids, kv_ids) pair form and its
    backward handles the tuple cotangent (float0 per element)."""
    rng = np.random.RandomState(7)
    b, t, h, d = 1, 24, 2, 8
    q, k, v = (jnp.asarray(rng.randn(b, t, h, d).astype(np.float32))
               for _ in range(3))
    ids = jnp.asarray(np.array([[0] * 10 + [1] * 14]), jnp.int32)

    out_pair = flash_attention(q, k, v, block_q=8, block_k=24,
                               segment_ids=(ids, ids))
    out_single = flash_attention(q, k, v, block_q=8, block_k=24,
                                 segment_ids=ids)
    np.testing.assert_allclose(np.asarray(out_pair),
                               np.asarray(out_single), rtol=1e-6)
    g = jax.grad(lambda q: (flash_attention(
        q, k, v, block_q=8, block_k=24,
        segment_ids=(ids, ids)) ** 2).sum())(q)
    assert np.isfinite(np.asarray(g)).all()


def test_segment_id_shape_validation_both_entry_points():
    from petastorm_tpu.ops.flash_attention import flash_attention_with_lse

    rng = np.random.RandomState(8)
    q = jnp.asarray(rng.randn(1, 16, 1, 8).astype(np.float32))
    k = jnp.asarray(rng.randn(1, 24, 1, 8).astype(np.float32))
    ids16 = jnp.zeros((1, 16), jnp.int32)
    ids24 = jnp.zeros((1, 24), jnp.int32)
    # single array + cross-length → both entry points raise
    with pytest.raises(ValueError, match="T_q == T_kv"):
        flash_attention(q, k, k, segment_ids=ids16)
    with pytest.raises(ValueError, match="T_q == T_kv"):
        flash_attention_with_lse(q, k, k, segment_ids=ids16)
    # swapped pair → raises rather than silently mis-masking
    with pytest.raises(ValueError, match="swapped"):
        flash_attention_with_lse(q, k, k, segment_ids=(ids24, ids16))
    # correct pair → runs
    out, lse = flash_attention_with_lse(q, k, k, block_q=16, block_k=24,
                                        segment_ids=(ids16, ids24))
    assert out.shape == (1, 16, 1, 8)


def test_single_segment_ids_length_mismatch_raises():
    rng = np.random.RandomState(9)
    q = jnp.asarray(rng.randn(1, 16, 1, 8).astype(np.float32))
    bad_ids = jnp.zeros((1, 24), jnp.int32)
    with pytest.raises(ValueError, match="does not match the sequence"):
        flash_attention(q, q, q, segment_ids=bad_ids)


# ---------------------------------------------------------------------------
# Grouped-query attention (GQA / MQA): k/v carry fewer heads than q
# ---------------------------------------------------------------------------

def _gqa_qkv(h=4, h_kv=2, b=2, t=48, d=16, seed=9):
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(b, t, h, d).astype(np.float32))
    k = jnp.asarray(rng.randn(b, t, h_kv, d).astype(np.float32))
    v = jnp.asarray(rng.randn(b, t, h_kv, d).astype(np.float32))
    return q, k, v


def _repeat_kv_oracle(h, h_kv, **okw):
    """GQA's defining equivalence: attention with the K/V heads repeated
    to the query head count."""
    g = h // h_kv

    def fn(q, k, v):
        return attention_reference(q, jnp.repeat(k, g, axis=2),
                                   jnp.repeat(v, g, axis=2), **okw)

    return fn


@pytest.mark.parametrize("h_kv", [2, 1])
@pytest.mark.parametrize("causal", [False, True])
def test_gqa_matches_repeated_kv_reference(h_kv, causal):
    q, k, v = _gqa_qkv(h_kv=h_kv)
    out = flash_attention(q, k, v, block_q=16, block_k=16, causal=causal)
    want = _repeat_kv_oracle(4, h_kv, causal=causal)(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_gqa_gradients_group_sum_matches_repeated_kv_autodiff():
    """dK/dV must come back at the K/V head count as the SUM over each
    group's q-heads — exactly what autodiff through the repeated-KV
    oracle produces for the un-repeated tensors."""
    q, k, v = _gqa_qkv(h_kv=2, seed=10)
    oracle = _repeat_kv_oracle(4, 2, causal=True)

    def loss_fl(q, k, v):
        return (flash_attention(q, k, v, block_q=16, block_k=16,
                                causal=True) ** 2).sum()

    def loss_ref(q, k, v):
        return (oracle(q, k, v) ** 2).sum()

    got = jax.grad(loss_fl, (0, 1, 2))(q, k, v)
    want = jax.grad(loss_ref, (0, 1, 2))(q, k, v)
    for name, a, b in zip(("dq", "dk", "dv"), got, want):
        assert a.shape == b.shape, name
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4, err_msg=name)


def test_gqa_composes_with_lengths_and_segments():
    q, k, v = _gqa_qkv(h_kv=2, seed=11)
    t = q.shape[1]
    lens = jnp.asarray([t - 10, t], jnp.int32)
    out = flash_attention(q, k, v, block_q=16, block_k=16, causal=True,
                          kv_lengths=lens)
    want = _repeat_kv_oracle(4, 2, causal=True, lengths=lens)(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    segs = jnp.asarray(np.repeat(np.arange(4), t // 4)[None]
                       .repeat(2, 0), jnp.int32)
    out = flash_attention(q, k, v, block_q=16, block_k=16, causal=True,
                          segment_ids=segs)
    want = _repeat_kv_oracle(4, 2, causal=True, segment_ids=segs)(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_gqa_with_lse_and_cotangent():
    from petastorm_tpu.ops.flash_attention import flash_attention_with_lse

    q, k, v = _gqa_qkv(h_kv=2, seed=12)
    out, lse = flash_attention_with_lse(q, k, v, block_q=16, block_k=16,
                                        causal=True)
    want = _repeat_kv_oracle(4, 2, causal=True)(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    assert lse.shape == (2, q.shape[1], 4)  # lse is per QUERY head

    def loss(q, k, v):
        o, l = flash_attention_with_lse(q, k, v, block_q=16, block_k=16,
                                        causal=True)
        return (o ** 2).sum() + (l * 0.01).sum()

    grads = jax.grad(loss, (0, 1, 2))(q, k, v)
    assert grads[1].shape == k.shape and grads[2].shape == v.shape
    assert all(bool(jnp.isfinite(g).all()) for g in grads)


def test_gqa_rejects_bad_head_ratios_and_reference_bwd():
    q, k, v = _gqa_qkv(h_kv=2)
    with pytest.raises(ValueError, match="group"):
        flash_attention(q, k[:, :, :1].repeat(3, axis=2),
                        v[:, :, :1].repeat(3, axis=2))  # 4 % 3 != 0
    with pytest.raises(ValueError, match="share"):
        flash_attention(q, k, v[:, :, :1])  # k/v head mismatch
    with pytest.raises(NotImplementedError, match="reference"):
        flash_attention(q, k, v, bwd_impl="reference")


# ---------------------------------------------------------------------------
# Length-aware block_k default (512 at T >= 4096, measured faster on v5e)
# ---------------------------------------------------------------------------

def test_default_blocks_resolution():
    from petastorm_tpu.ops.flash_attention import _default_blocks

    assert _default_blocks(1024, None, None) == (128, 128)
    assert _default_blocks(4095, None, None) == (128, 128)
    assert _default_blocks(4096, None, None) == (128, 512)
    assert _default_blocks(8192, 64, None) == (64, 512)
    # explicit sizes always win
    assert _default_blocks(8192, None, 128) == (128, 128)
    assert _default_blocks(8192, 256, 256) == (256, 256)


def test_long_t_auto_block_matches_reference():
    """T=4096 crosses the auto threshold — the shipping default
    (block_k=512) must stay oracle-exact, forward and backward."""
    rng = np.random.RandomState(40)
    q, k, v = (jnp.asarray(rng.randn(1, 4096, 1, 8).astype(np.float32))
               for _ in range(3))
    out = flash_attention(q, k, v, causal=True)
    want = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    got = jax.grad(lambda a, b, c: (flash_attention(a, b, c, causal=True)
                                    ** 2).sum(), (0, 1, 2))(q, k, v)
    ref = jax.grad(lambda a, b, c: (attention_reference(a, b, c,
                                                        causal=True)
                                    ** 2).sum(), (0, 1, 2))(q, k, v)
    for name, a, b in zip(("dq", "dk", "dv"), got, ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4, err_msg=name)


@pytest.mark.parametrize("backend,expect", [("cpu", True), ("tpu", False),
                                            ("gpu", None)])
def test_interpret_auto_is_cpu_only(monkeypatch, backend, expect):
    """Auto interpret mode is the CPU backend's alone: a backend without a
    kernel raises instead of degrading silently into the interpreter."""
    import importlib

    fa_module = importlib.import_module("petastorm_tpu.ops.flash_attention")
    monkeypatch.setattr(fa_module.jax, "default_backend", lambda: backend)
    if expect is None:
        with pytest.raises(RuntimeError, match="no kernel for backend"):
            fa_module._should_interpret()
    else:
        assert fa_module._should_interpret() is expect
