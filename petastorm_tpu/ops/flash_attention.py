"""Pallas TPU flash attention: tiled online-softmax attention in VMEM.

The hot op of the sequence model (``models/sequence_model.py`` — NGram
``[B, T, H, D]`` windows). The reference has no accelerator code; this is
the TPU-native answer to "where do the FLOPs go": Q/K/V tiles stream
HBM → VMEM block by block, scores hit the MXU per tile
(``preferred_element_type=f32``), and the online softmax keeps running
``(max, sum, acc)`` statistics in VMEM scratch so the [T, T] score matrix is
NEVER materialized — memory O(block_q × block_k) instead of O(T²).

Layout/tiling choices (pallas_guide.md):

- grid = (batch·heads, Tq/block_q, Tk/block_k) — the last axis iterates
  innermost and sequentially on TPU, which is what makes scratch
  accumulation across K blocks valid;
- softmax statistics live in ``(block_q, 128)`` f32 scratch (lane-broadcast:
  min tile is 8×128, a [block_q]-vector would not tile);
- block sizes default to 128 to match the MXU's 128×128 systolic array; the
  head dim should be a multiple of 128 for full MXU rate (Mosaic pads
  smaller dims at reduced efficiency);
- sequence lengths that don't divide the block are zero-padded in the
  wrapper and masked to -inf inside the kernel via a 2D
  ``broadcasted_iota`` (1D iota does not lower on TPU).

Backward: hand-tiled flash-2 style ``jax.custom_vjp`` — the forward emits
the per-row log-sum-exp as a residual, and two Pallas kernels recompute the
probabilities per (Q-block, K-block) tile from (q, k, lse): one sweep
accumulates dQ over K blocks, the other accumulates dK/dV over Q blocks.
Like the forward, no kernel ever materializes the [T, T] score matrix, so
training memory is O(block_q × block_k) + O(T·D) residuals — not O(T²).
The pre-round-4 recompute-through-the-reference backward is kept as a
correctness oracle behind ``bwd_impl="reference"``.

Off-TPU (tests, CPU dev) the kernel runs in interpret mode; the Mosaic
lowering is exercised on real TPU by the driver benchmark's flash legs
(``bench.py`` ``flash_numerics``: forward + backward for causal /
kv_lengths / segment_ids / with_lse vs a float64 dense oracle, and
``flash_memsweep``: the O(block²)-vs-O(T²) training-memory claim as
measured OOM ceilings — ``BENCH_r05.json`` ``flash_kernel``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_LANES = 128  # TPU lane width: scratch min-tile last dim


def _attention_reference(q, k, v, causal=False):
    """Unfused oracle over ``[B, T, H, D]`` (same numerics contract as the
    kernel); used by the recompute backward."""
    scale = 1.0 / jnp.sqrt(jnp.array(q.shape[-1], jnp.float32))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    row_valid = None
    if causal:
        t_q, t_k = scores.shape[-2], scores.shape[-1]
        row = jnp.arange(t_q)[:, None] + (t_k - t_q)  # align last positions
        mask = jnp.arange(t_k)[None, :] <= row
        # Rows with no valid key (t_q > t_kv suffix alignment) must produce
        # ZERO output, nan-free in both forward and vjp: substitute finite
        # scores for those rows, then zero their probabilities.
        row_valid = mask.any(axis=-1, keepdims=True)
        scores = jnp.where(mask, scores, -jnp.inf)
        scores = jnp.where(row_valid, scores, 0.0)
    probs = jax.nn.softmax(scores, axis=-1)
    if row_valid is not None:
        probs = jnp.where(row_valid, probs, 0.0)
    return jnp.einsum("bhqk,bkhd->bqhd", probs,
                      v.astype(jnp.float32)).astype(q.dtype)


def _dot_precision(dtype):
    """MXU multiply precision: f32 inputs get the full-precision passes
    (DEFAULT is single-pass bf16 — ~1e-2 relative error that softmax's exp
    amplifies); bf16 inputs are exact at DEFAULT (they started as bf16)."""
    return (jax.lax.Precision.HIGHEST if dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)


def _kv_limit(lens_ref, kv_len):
    """Effective key-count bound for the column mask: the static padded-KV
    bound, or — with per-example lengths — this batch·head's dynamic count
    read as an SMEM scalar (broadcasts against the (block_q, block_k) ids
    exactly like the static int)."""
    if lens_ref is None:
        return kv_len
    from jax.experimental import pallas as pl

    return jnp.minimum(lens_ref[pl.program_id(0)], kv_len)


def _flash_kernel(*refs, sm_scale, block_q, block_k, kv_len, causal_offset,
                  emit_lse, has_lens, has_segs, precision):
    from jax.experimental import pallas as pl

    if has_lens:
        q_ref, k_ref, v_ref, lens_ref = refs[:4]
        rest = refs[4:]
    else:
        q_ref, k_ref, v_ref = refs[:3]
        lens_ref = None
        rest = refs[3:]
    if has_segs:
        qseg_ref, kvseg_ref = rest[:2]
        rest = rest[2:]
    else:
        qseg_ref = kvseg_ref = None
    o_ref = rest[0]
    rest = rest[1:]
    if emit_lse:
        lse_ref, m_scratch, l_scratch, acc_scratch = rest
    else:
        lse_ref = None
        m_scratch, l_scratch, acc_scratch = rest

    qb = pl.program_id(1)
    kb = pl.program_id(2)
    last_kb = pl.num_programs(2) - 1
    # Read outside the pl.when wrapper: program_id inside a when-body does
    # not lower in interpret mode.
    kv_limit = _kv_limit(lens_ref, kv_len)

    @pl.when(kb == 0)
    def _init():
        m_scratch[...] = jnp.full_like(m_scratch, -jnp.inf)
        l_scratch[...] = jnp.zeros_like(l_scratch)
        acc_scratch[...] = jnp.zeros_like(acc_scratch)

    def compute_block():
        q = q_ref[0].astype(jnp.float32)          # [block_q, d]
        k = k_ref[0].astype(jnp.float32)          # [block_k, d]
        v = v_ref[0].astype(jnp.float32)

        s = _masked_scores(q, k, kb, qb, sm_scale=sm_scale, block_q=block_q,
                           block_k=block_k, kv_len=kv_limit,
                           causal_offset=causal_offset,
                           precision=precision,
                           q_seg=None if qseg_ref is None else qseg_ref[0],
                           kv_seg=(None if kvseg_ref is None
                                   else kvseg_ref[0, :1]))

        m_prev = m_scratch[...][:, :1]            # [block_q, 1]
        l_prev = l_scratch[...][:, :1]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        # A row can be fully masked in this block (causal + partial-overlap
        # K blocks): m_new stays -inf and the raw exponent would be
        # (-inf) - (-inf) = nan.
        fully_masked = m_new == -jnp.inf
        m_safe = jnp.where(fully_masked, 0.0, m_new)
        alpha = jnp.where(fully_masked, 1.0, jnp.exp(m_prev - m_safe))
        p = jnp.exp(s - m_safe)               # [block_q, block_k]; -inf -> 0
        l_new = alpha * l_prev + p.sum(axis=1, keepdims=True)

        acc_scratch[...] = acc_scratch[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
                                 precision=precision)
        m_scratch[...] = jnp.broadcast_to(m_new, m_scratch.shape)
        l_scratch[...] = jnp.broadcast_to(l_new, l_scratch.shape)

    if causal_offset is None:
        compute_block()
    else:
        # Skip K blocks that lie entirely above the causal boundary for this
        # Q block (the grid's last axis runs sequentially, so scratch state
        # carries across the skipped steps) — ~2x compute saved at large T.
        last_valid_col = qb * block_q + causal_offset + block_q - 1
        pl.when(kb * block_k <= last_valid_col)(compute_block)

    @pl.when(kb == last_kb)
    def _emit():
        l = l_scratch[...][:, :1]
        o_ref[0] = (acc_scratch[...] / jnp.maximum(l, 1e-30)) \
            .astype(o_ref.dtype)
        if lse_ref is not None:
            # Per-row log-sum-exp residual for the flash backward. Rows with
            # no valid key (causal cross-length) have l == 0: +inf makes the
            # backward's exp(s - lse) an exact zero with no inf-inf nan.
            lf = l_scratch[...]
            lse_ref[0] = jnp.where(
                lf > 0.0,
                m_scratch[...] + jnp.log(jnp.maximum(lf, 1e-37)),
                jnp.inf)


def _to_bh(x):
    """[B, T, H, D] → [B·H, T, D] (attention is independent per batch·head)."""
    b, t, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, t, d)


def _from_bh(x, b, h):
    bh, t, d = x.shape
    return x.reshape(b, h, t, d).transpose(0, 2, 1, 3)


def _pad_t(x, block):
    pad = (-x.shape[1]) % block
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
    return x


def _lens_to_bh(kv_lengths, b, h):
    """[B] int lengths → per-batch·head [B·H] int32 (bh index is
    batch-major, matching :func:`_to_bh`); consumed as SMEM scalars."""
    return jnp.repeat(kv_lengths.astype(jnp.int32), h)


def _lens_spec(pl, pltpu, n_bh):
    # The whole [B·H] vector in SMEM every step (rank-1 blocks must be the
    # full array); the kernel indexes it with program_id(0). A scalar read
    # broadcasts natively in the comparison against the id tiles (a VMEM
    # (1, 1) tile would need a both-axes broadcast Mosaic doesn't
    # implement).
    return pl.BlockSpec((n_bh,), lambda bh, i, j: (0,),
                        memory_space=pltpu.SMEM)


_SUBLANES = 8  # TPU sublane width: kv-segment-id second-to-last dim


def _pad_seg_row(segment_ids, block):
    """[B, T] int segment ids → [B, T_padded] int32. The pad value is
    irrelevant to masking (padded KV columns die on the kv_len mask, padded
    Q rows are sliced off), it only has to exist."""
    seg = jnp.asarray(segment_ids, jnp.int32)
    pad = (-seg.shape[1]) % block
    if pad:
        seg = jnp.pad(seg, ((0, 0), (0, pad)), constant_values=-1)
    return seg


def _split_segment_ids(segment_ids):
    """``segment_ids`` is one [B, T] array (self-attention over a packed
    batch) or a ``(q_ids [B, Tq], kv_ids [B, Tkv])`` pair (the flash ring:
    resident K/V blocks carry their own ids)."""
    if isinstance(segment_ids, (tuple, list)):
        q_ids, kv_ids = segment_ids
        return q_ids, kv_ids
    return segment_ids, segment_ids


def _check_segment_ids(segment_ids, t_q, t_kv):
    """Per-side length validation for both segment-id forms — a mismatched
    array would silently mis-mask (ids sliced/padded against the wrong
    positions), so it must raise instead."""
    if isinstance(segment_ids, (tuple, list)):
        q_ids, kv_ids = segment_ids
        for name, ids in (("q_ids", q_ids), ("kv_ids", kv_ids)):
            if len(jnp.shape(ids)) != 2:
                raise ValueError(
                    f"segment_ids {name} must be [B, T] (batch axis "
                    f"included), got shape {jnp.shape(ids)}")
        if jnp.shape(q_ids)[1] != t_q or jnp.shape(kv_ids)[1] != t_kv:
            raise ValueError(
                f"segment_ids pair shapes {jnp.shape(q_ids)} / "
                f"{jnp.shape(kv_ids)} do not match T_q={t_q} / "
                f"T_kv={t_kv} (is the (q_ids, kv_ids) order swapped?)")
    else:
        if len(jnp.shape(segment_ids)) != 2:
            raise ValueError(
                f"segment_ids must be [B, T] (batch axis included — "
                f"per-token ids alone are ambiguous across the batch), "
                f"got shape {jnp.shape(segment_ids)}")
        if t_q != t_kv:
            raise ValueError(
                f"a single segment_ids array requires T_q == T_kv "
                f"(self-attention over a packed batch), got {t_q} vs "
                f"{t_kv}; pass a (q_ids, kv_ids) pair for cross-length "
                "attention")
        if jnp.shape(segment_ids)[1] != t_q:
            raise ValueError(
                f"segment_ids shape {jnp.shape(segment_ids)} does not "
                f"match the sequence length T={t_q}")


def _q_segs_arr(segment_ids, block_q):
    """[B, T] → lane-broadcast [B, Tq_pad, 128]: a (block_q, 128) tile
    satisfies the TPU min-tile rule where a (1, block_q) row would not."""
    seg = _pad_seg_row(segment_ids, block_q)
    return jax.lax.broadcast_in_dim(
        seg, (seg.shape[0], seg.shape[1], _LANES), (0, 1))


def _kv_segs_arr(segment_ids, block_k):
    """[B, T] → sublane-broadcast [B, 8, Tkv_pad]: an (8, block_k) tile
    keeps the ids on the LANE axis, where the kernel compares them against
    the lane-major score columns without a transpose."""
    seg = _pad_seg_row(segment_ids, block_k)
    return jax.lax.broadcast_in_dim(
        seg, (seg.shape[0], _SUBLANES, seg.shape[1]), (0, 2))


def _q_seg_spec(pl, pltpu, h, block_q, q_block_of):
    """Tile of the lane-broadcast q segment ids; the batch coordinate is
    bh // h (ids are per batch, the grid is per batch·head) and the token
    block must ride the same (possibly clamped) fetch as its Q tile."""
    return pl.BlockSpec(
        (1, block_q, _LANES),
        lambda bh, i, j: (bh // h, q_block_of(i, j), 0),
        memory_space=pltpu.VMEM)


def _kv_seg_spec(pl, pltpu, h, block_k, kv_block_of):
    return pl.BlockSpec(
        (1, _SUBLANES, block_k),
        lambda bh, i, j: (bh // h, 0, kv_block_of(i, j)),
        memory_space=pltpu.VMEM)


def _kv_bh_map(h, h_kv):
    """Grid-coordinate map for grouped-query attention: the grid iterates
    q-heads (``bh = b·h + hq``), and each group of ``h // h_kv`` q-heads
    reads the SAME K/V head — the map lands their fetches on its flattened
    coordinate ``b·h_kv + hq // group``. Identity when ``h == h_kv``
    (the arithmetic reduces to ``bh``), so one code path serves both."""
    group = h // h_kv

    def kv_bh(bh):
        return (bh // h) * h_kv + (bh % h) // group

    return kv_bh


def _check_gqa_heads(q, k, v, bwd_impl=None):
    """Validate the grouped-query head contract: K and V share a head
    count that divides Q's. Returns ``(h, h_kv)``."""
    h, h_kv = q.shape[2], k.shape[2]
    if v.shape[2] != h_kv:
        raise ValueError(
            f"k has {h_kv} heads but v has {v.shape[2]}; K and V must "
            "share their (possibly grouped) head count")
    if h % h_kv:
        raise ValueError(
            f"{h} query heads do not group over {h_kv} K/V heads "
            "(grouped-query attention requires h % h_kv == 0)")
    if bwd_impl == "reference" and h_kv != h:
        raise NotImplementedError(
            "bwd_impl='reference' does not support grouped-query K/V "
            "(the dense oracle is single-ratio); repeat K/V to the query "
            "head count for the oracle, or use bwd_impl='flash'")
    return h, h_kv


def _group_sum_kv_grad(grad_bh, b, h, h_kv, t_kv):
    """Per-q-head dK/dV partials ``[B·H, Tk_pad, D]`` → ``[B, Tk, h_kv,
    D]``: each K/V head's gradient is the sum over its q-head group
    (f32 accumulation — a bf16 group-sum would round between partials).
    The ungrouped path short-circuits to the plain reshape/transpose so
    standard MHA backward keeps its exact pre-GQA form (no f32 transient
    at the memory-sweep ceiling)."""
    if h == h_kv:
        return _from_bh(grad_bh[:, :t_kv], b, h)
    d = grad_bh.shape[-1]
    g = grad_bh[:, :t_kv].reshape(b, h_kv, h // h_kv, t_kv, d)
    g = g.astype(jnp.float32).sum(axis=2)
    return g.transpose(0, 2, 1, 3).astype(grad_bh.dtype)


def _check_seg_blocks(block_k):
    if block_k > _LANES and block_k % _LANES:
        raise ValueError(
            f"segment_ids requires block_k <= {_LANES} or a multiple of "
            f"{_LANES} (the lane-tiled id compare), got {block_k}")


def _flash_forward(q, k, v, block_q, block_k, interpret, causal=False,
                   return_residuals=False, kv_lengths=None,
                   segment_ids=None, causal_shift=0):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    orig_dtype = q.dtype
    b, t_q, h, d = q.shape
    t_kv = k.shape[1]
    h_kv = k.shape[2]
    kv_bh = _kv_bh_map(h, h_kv)

    qf = _pad_t(_to_bh(q), block_q)
    kf = _pad_t(_to_bh(k), block_k)
    vf = _pad_t(_to_bh(v), block_k)
    tq_p, tk_p = qf.shape[1], kf.shape[1]

    grid = (b * h, tq_p // block_q, tk_p // block_k)
    # causal_shift slides the diagonal: -1 = strict causal (k strictly
    # before q) — the striped-ring blocks where the key shard sits "after"
    # the query shard in the interleaved global order.
    causal_offset = (t_kv - t_q + causal_shift) if causal else None
    kernel = functools.partial(
        _flash_kernel,
        sm_scale=1.0 / float(d) ** 0.5,
        block_q=block_q,
        block_k=block_k,
        kv_len=t_kv,
        # Align the LAST query with the LAST key (suffix-query convention).
        causal_offset=causal_offset,
        emit_lse=return_residuals,
        has_lens=kv_lengths is not None,
        has_segs=segment_ids is not None,
        precision=_dot_precision(orig_dtype),
    )
    if causal_offset is None:
        kv_block = lambda i, j: j  # noqa: E731
    else:
        def kv_block(i, j):
            # Clamp skipped (fully-above-causal-boundary) K/V fetches to the
            # last USEFUL block for this Q block: pl.when skips their
            # compute, and an unchanged block index lets the pipeline skip
            # the HBM->VMEM copy too — the skip saves bandwidth, not just
            # MXU time.
            last = (i * block_q + causal_offset + block_q - 1) // block_k
            return jnp.minimum(j, jnp.maximum(last, 0))

    kv_index = lambda bh, i, j: (kv_bh(bh), kv_block(i, j), 0)  # noqa: E731
    q_index = lambda bh, i, j: (bh, i, 0)  # noqa: E731
    out_shape = jax.ShapeDtypeStruct((b * h, tq_p, d), orig_dtype)
    out_specs = pl.BlockSpec((1, block_q, d), q_index,
                             memory_space=pltpu.VMEM)
    if return_residuals:
        out_shape = (out_shape,
                     jax.ShapeDtypeStruct((b * h, tq_p, _LANES), jnp.float32))
        out_specs = (out_specs,
                     pl.BlockSpec((1, block_q, _LANES), q_index,
                                  memory_space=pltpu.VMEM))

    in_specs = [
        pl.BlockSpec((1, block_q, d), q_index,
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_k, d), kv_index,
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_k, d), kv_index,
                     memory_space=pltpu.VMEM),
    ]
    inputs = [qf, kf, vf]
    if kv_lengths is not None:
        in_specs.append(_lens_spec(pl, pltpu, b * h))
        inputs.append(_lens_to_bh(kv_lengths, b, h))
    if segment_ids is not None:
        _check_seg_blocks(block_k)
        q_ids, kv_ids = _split_segment_ids(segment_ids)
        in_specs.append(_q_seg_spec(pl, pltpu, h, block_q,
                                    lambda i, j: i))
        in_specs.append(_kv_seg_spec(pl, pltpu, h, block_k, kv_block))
        inputs.extend([_q_segs_arr(q_ids, block_q),
                       _kv_segs_arr(kv_ids, block_k)])

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),  # running max
            pltpu.VMEM((block_q, _LANES), jnp.float32),  # running sum
            pltpu.VMEM((block_q, d), jnp.float32),       # output accumulator
        ],
        interpret=interpret,
    )(*inputs)

    if return_residuals:
        out_padded, lse = out
        # lse is lane-broadcast (all 128 lanes equal); store one column.
        return out_padded, lse[:, :, 0]
    return _from_bh(out[:, :t_q, :], b, h)


def _masked_scores(q, k, kb, qb, *, sm_scale, block_q, block_k, kv_len,
                   causal_offset, precision, q_seg=None, kv_seg=None):
    """Recompute the masked score tile s = mask(scale·q kᵀ) for one
    (Q-block, K-block) pair — shared by both backward kernels; identical
    masking semantics to the forward kernel. ``q_seg``/``kv_seg``:
    optional ``(1, block)`` int32 segment-id tiles — positions in different
    segments (packed sequences) never attend to each other."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32,
                                 precision=precision) * sm_scale
    col_ids = kb * block_k + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, dimension=1)
    s = jnp.where(col_ids < kv_len, s, -jnp.inf)
    if causal_offset is not None:
        row_ids = (qb * block_q + causal_offset
                   + jax.lax.broadcasted_iota(jnp.int32, s.shape,
                                              dimension=0))
        s = jnp.where(col_ids <= row_ids, s, -jnp.inf)
    if q_seg is not None:
        # q_seg: [block_q, 128] lane-broadcast; kv_seg: [1, block_k]. Slice
        # or lane-tile q's ids to block_k columns, then a broadcast compare
        # yields the [block_q, block_k] same-segment mask (upstream TPU
        # flash-attention idiom — no transpose, MXU-friendly layouts).
        lanes = q_seg.shape[1]
        if block_k <= lanes:
            qs = q_seg[:, :block_k]
        else:
            qs = jnp.tile(q_seg, (1, block_k // lanes))
        s = jnp.where(qs == kv_seg, s, -jnp.inf)
    return s


def _split_bwd_refs(refs, has_lens, has_segs):
    """Unpack a backward kernel's refs: 6 fixed inputs (q, k, v, do, o,
    lse), then the optional lens / segment-id inputs, then outputs+scratch."""
    q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref = refs[:6]
    idx = 6
    lens_ref = None
    if has_lens:
        lens_ref = refs[idx]
        idx += 1
    qseg_ref = kvseg_ref = None
    if has_segs:
        qseg_ref, kvseg_ref = refs[idx:idx + 2]
        idx += 2
    return (q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, lens_ref,
            qseg_ref, kvseg_ref, refs[idx:])


def _flash_bwd_dq_kernel(*refs, sm_scale, block_q, block_k, kv_len,
                         causal_offset, has_lens, has_segs, has_dlse,
                         precision):
    """dQ sweep: grid (B·H, Tq/block_q, Tk/block_k) — K blocks iterate
    innermost, dq accumulates in VMEM scratch. Per tile:
    p = exp(s - lse); ds = p·(do·vᵀ - Δ [+ dlse])·scale; dq += ds·k, with
    Δ = rowsum(do ∘ o) recomputed from the residuals (O(block·d), cheaper
    than staging a third stats tensor). ``dlse`` is the cotangent of the
    emitted log-sum-exp when the caller consumed it (ring merging):
    ∂lse_i/∂s_ij = p_ij, so it adds inside the parenthesis."""
    from jax.experimental import pallas as pl

    (q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, lens_ref, qseg_ref,
     kvseg_ref, rest) = _split_bwd_refs(refs, has_lens, has_segs)
    if has_dlse:
        dlse_ref = rest[0]
        rest = rest[1:]
    else:
        dlse_ref = None
    dq_ref, dq_acc = rest
    kv_len = _kv_limit(lens_ref, kv_len)

    qb = pl.program_id(1)
    kb = pl.program_id(2)
    last_kb = pl.num_programs(2) - 1

    @pl.when(kb == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def compute_block():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        o = o_ref[0].astype(jnp.float32)

        s = _masked_scores(q, k, kb, qb, sm_scale=sm_scale, block_q=block_q,
                           block_k=block_k, kv_len=kv_len,
                           causal_offset=causal_offset,
                           precision=precision,
                           q_seg=None if qseg_ref is None else qseg_ref[0],
                           kv_seg=(None if kvseg_ref is None
                                   else kvseg_ref[0, :1]))
        # lse is +inf for rows with no valid key, so every term is an exact
        # zero (finite-or-(-inf) minus +inf → -inf → exp 0; never inf-inf).
        p = jnp.exp(s - lse_ref[0][:, :1])
        delta = (do * o).sum(axis=1, keepdims=True)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32,
                                 precision=precision)
        inner = dp - delta
        if dlse_ref is not None:
            inner = inner + dlse_ref[0][:, :1]
        ds = p * inner * sm_scale
        dq_acc[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
                                 precision=precision)

    if causal_offset is None:
        compute_block()
    else:
        last_valid_col = qb * block_q + causal_offset + block_q - 1
        pl.when(kb * block_k <= last_valid_col)(compute_block)

    @pl.when(kb == last_kb)
    def _emit():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(*refs, sm_scale, block_q, block_k, kv_len,
                          causal_offset, has_lens, has_segs, has_dlse,
                          precision):
    """dK/dV sweep: grid (B·H, Tk/block_k, Tq/block_q) — Q blocks iterate
    innermost, dk/dv accumulate in VMEM scratch. Per tile:
    dv += pᵀ·do; dk += dsᵀ·q (same recomputed p/ds as the dQ sweep,
    including the optional dlse term)."""
    from jax.experimental import pallas as pl

    (q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, lens_ref, qseg_ref,
     kvseg_ref, rest) = _split_bwd_refs(refs, has_lens, has_segs)
    if has_dlse:
        dlse_ref = rest[0]
        rest = rest[1:]
    else:
        dlse_ref = None
    dk_ref, dv_ref, dk_acc, dv_acc = rest
    kv_len = _kv_limit(lens_ref, kv_len)

    kb = pl.program_id(1)
    qb = pl.program_id(2)
    last_qb = pl.num_programs(2) - 1

    @pl.when(qb == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def compute_block():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        o = o_ref[0].astype(jnp.float32)

        s = _masked_scores(q, k, kb, qb, sm_scale=sm_scale, block_q=block_q,
                           block_k=block_k, kv_len=kv_len,
                           causal_offset=causal_offset,
                           precision=precision,
                           q_seg=None if qseg_ref is None else qseg_ref[0],
                           kv_seg=(None if kvseg_ref is None
                                   else kvseg_ref[0, :1]))
        p = jnp.exp(s - lse_ref[0][:, :1])
        dv_acc[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
                                 precision=precision)
        delta = (do * o).sum(axis=1, keepdims=True)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32,
                                 precision=precision)
        inner = dp - delta
        if dlse_ref is not None:
            inner = inner + dlse_ref[0][:, :1]
        ds = p * inner * sm_scale
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
                                 precision=precision)

    if causal_offset is None:
        compute_block()
    else:
        # Q block qb touches K block kb iff its causal boundary reaches it.
        last_valid_col = qb * block_q + causal_offset + block_q - 1
        pl.when(last_valid_col >= kb * block_k)(compute_block)

    @pl.when(qb == last_qb)
    def _emit():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _flash_backward(q, k, v, o_padded, lse, g, block_q, block_k, interpret,
                    causal, kv_lengths=None, segment_ids=None,
                    causal_shift=0, dlse=None):
    """Flash-2 backward: two pallas sweeps, O(block²) VMEM, no [T, T]
    buffer. ``o_padded``/``lse`` are [B·H, Tq_padded(, )] residuals from the
    forward; q/k/v are the user-shaped [B, T, H, D] primals."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, t_q, h, d = q.shape
    t_kv = k.shape[1]
    h_kv = k.shape[2]
    kv_bh = _kv_bh_map(h, h_kv)

    qf = _pad_t(_to_bh(q), block_q)
    kf = _pad_t(_to_bh(k), block_k)
    vf = _pad_t(_to_bh(v), block_k)
    dof = _pad_t(_to_bh(g), block_q)
    tq_p, tk_p = qf.shape[1], kf.shape[1]
    n_qb, n_kb = tq_p // block_q, tk_p // block_k

    # Rebroadcast the stored lse column across the lane dim so backward
    # loads see the same Mosaic-friendly (block_q, 128) layout the forward
    # scratch used (a [block_q]-vector would not tile).
    lse_b = jnp.broadcast_to(lse[:, :, None], (b * h, tq_p, _LANES))

    lens_inputs, lens_specs = [], []
    if kv_lengths is not None:
        lens_inputs = [_lens_to_bh(kv_lengths, b, h)]
        lens_specs = [_lens_spec(pl, pltpu, b * h)]
    seg_inputs = []
    if segment_ids is not None:
        _check_seg_blocks(block_k)
        q_ids, kv_ids = _split_segment_ids(segment_ids)
        seg_inputs = [_q_segs_arr(q_ids, block_q),
                      _kv_segs_arr(kv_ids, block_k)]
    dlse_inputs = []
    if dlse is not None:
        # The lse cotangent, lane-broadcast like the lse residual itself
        # ([B·H, Tq_pad] from the vjp wrapper).
        dlse_inputs = [jnp.broadcast_to(dlse[:, :, None],
                                        (b * h, tq_p, _LANES))]

    causal_offset = (t_kv - t_q + causal_shift) if causal else None
    common = dict(sm_scale=1.0 / float(d) ** 0.5, block_q=block_q,
                  block_k=block_k, kv_len=t_kv, causal_offset=causal_offset,
                  has_lens=kv_lengths is not None,
                  has_segs=segment_ids is not None,
                  has_dlse=dlse is not None,
                  precision=_dot_precision(q.dtype))

    q_spec = lambda ix: pl.BlockSpec((1, block_q, d), ix,  # noqa: E731
                                     memory_space=pltpu.VMEM)
    kv_spec = lambda ix: pl.BlockSpec((1, block_k, d), ix,  # noqa: E731
                                      memory_space=pltpu.VMEM)

    # --- dQ sweep: (bh, qb, kb), K innermost --------------------------------
    dq_q_index = lambda bh, i, j: (bh, i, 0)  # noqa: E731
    if causal_offset is None:
        dq_kv_block = lambda i, j: j  # noqa: E731
    else:
        def dq_kv_block(i, j):
            # Clamp fetches of skipped (fully-future) K/V blocks, exactly as
            # in the forward, so the pipeline skips the copy too.
            last = (i * block_q + causal_offset + block_q - 1) // block_k
            return jnp.minimum(j, jnp.maximum(last, 0))

    dq_kv_index = \
        lambda bh, i, j: (kv_bh(bh), dq_kv_block(i, j), 0)  # noqa: E731
    dq_stats_spec = pl.BlockSpec((1, block_q, _LANES), dq_q_index,
                                 memory_space=pltpu.VMEM)
    dq_seg_specs = []
    if segment_ids is not None:
        dq_seg_specs = [_q_seg_spec(pl, pltpu, h, block_q,
                                    lambda i, j: i),
                        _kv_seg_spec(pl, pltpu, h, block_k, dq_kv_block)]

    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, **common),
        grid=(b * h, n_qb, n_kb),
        in_specs=[
            q_spec(dq_q_index),
            kv_spec(dq_kv_index),
            kv_spec(dq_kv_index),
            q_spec(dq_q_index),                      # do
            q_spec(dq_q_index),                      # o
            dq_stats_spec,                           # lse
        ] + lens_specs + dq_seg_specs + (
            # dlse must ride the EXACT same fetch as lse (same Q block).
            [dq_stats_spec] if dlse is not None else []),
        out_specs=q_spec(dq_q_index),
        out_shape=jax.ShapeDtypeStruct((b * h, tq_p, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
    )(qf, kf, vf, dof, o_padded, lse_b, *lens_inputs, *seg_inputs,
      *dlse_inputs)

    # --- dK/dV sweep: (bh, kb, qb), Q innermost -----------------------------
    # The grid stays per Q-HEAD: each grid row reads its group's shared K/V
    # block (kv_bh-mapped INPUT fetch) but writes its OWN per-q-head dk/dv
    # partial (un-mapped OUTPUT index) — grouped heads writing one output
    # block from different grid rows would race; the wrapper group-sums the
    # partials instead.
    dkv_kv_in_index = lambda bh, i, j: (kv_bh(bh), i, 0)  # noqa: E731
    dkv_kv_index = lambda bh, i, j: (bh, i, 0)  # noqa: E731
    if causal_offset is None:
        dkv_q_block = lambda i, j: j  # noqa: E731
    else:
        def dkv_q_block(i, j):
            # First Q block whose causal boundary reaches K block i; clamp
            # skipped earlier-Q fetches to it (ceil with floor-division).
            first = -((causal_offset + block_q - 1 - i * block_k) // block_q)
            first = jnp.clip(first, 0, n_qb - 1)
            return jnp.maximum(j, first)

    dkv_q_index = lambda bh, i, j: (bh, dkv_q_block(i, j), 0)  # noqa: E731
    dkv_stats_spec = pl.BlockSpec((1, block_q, _LANES), dkv_q_index,
                                  memory_space=pltpu.VMEM)
    dkv_seg_specs = []
    if segment_ids is not None:
        dkv_seg_specs = [_q_seg_spec(pl, pltpu, h, block_q, dkv_q_block),
                         _kv_seg_spec(pl, pltpu, h, block_k,
                                      lambda i, j: i)]

    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, **common),
        grid=(b * h, n_kb, n_qb),
        in_specs=[
            q_spec(dkv_q_index),
            kv_spec(dkv_kv_in_index),
            kv_spec(dkv_kv_in_index),
            q_spec(dkv_q_index),                     # do
            q_spec(dkv_q_index),                     # o
            dkv_stats_spec,                          # lse
        ] + lens_specs + dkv_seg_specs + (
            # dlse must ride the EXACT same fetch as lse (same Q block).
            [dkv_stats_spec] if dlse is not None else []),
        out_specs=(kv_spec(dkv_kv_index), kv_spec(dkv_kv_index)),
        out_shape=(jax.ShapeDtypeStruct((b * h, tk_p, d), k.dtype),
                   jax.ShapeDtypeStruct((b * h, tk_p, d), v.dtype)),
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        interpret=interpret,
    )(qf, kf, vf, dof, o_padded, lse_b, *lens_inputs, *seg_inputs,
      *dlse_inputs)

    dq = _from_bh(dq[:, :t_q], b, h)
    dk = _group_sum_kv_grad(dk, b, h, h_kv, t_kv)
    dv = _group_sum_kv_grad(dv, b, h, h_kv, t_kv)
    return dq, dk, dv


def _should_interpret():
    """Mosaic lowering on TPU; the interpreter only on the CPU backend (the
    tests). Any other backend raises: a chip that failed to come up must not
    degrade silently into the Pallas interpreter."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"flash_attention has no kernel for backend {backend!r}; pass "
        "interpret=True explicitly to run the Pallas interpreter there")


# Length-aware block_k default, measured on v5e (alternating A/B, fwd+bwd
# at bf16): 512 beats 128 by ~1.05x at T=8192 and ~1.35x at T=16384 — 4x
# fewer K-grid steps amortize the per-block revisit overhead — while 128
# stays right below the threshold (min-tile padding waste, and short-T
# shapes often don't divide 512).
_LONG_T_BLOCK_K = 512
_LONG_T_THRESHOLD = 4096


def _default_blocks(t_kv, block_q, block_k):
    """Resolve ``None`` block sizes (the public wrappers call this BEFORE
    the custom_vjp captures them, so forward and backward always agree)."""
    if block_q is None:
        block_q = 128
    if block_k is None:
        block_k = (_LONG_T_BLOCK_K if t_kv >= _LONG_T_THRESHOLD
                   else 128)
    return block_q, block_k


def flash_attention(q, k, v, block_q=None, block_k=None, interpret=None,
                    causal=False, bwd_impl="flash", kv_lengths=None,
                    segment_ids=None):
    """Tiled attention over ``[B, T, H, D]`` tensors; matches
    ``attention_reference`` numerics (f32 softmax) without materializing the
    ``[T, T]`` score matrix — in the forward OR the backward.

    :param block_q / block_k: VMEM tile sizes (``None`` = auto: 128, with
        ``block_k`` rising to 512 once ``T_kv`` reaches 4096 — measured
        faster on v5e at long T; see ``_default_blocks``).
    :param interpret: force the pallas interpreter (None = auto: Mosaic on
        TPU, interpreter on CPU, an error on any other backend).
    :param causal: mask key positions after each query's (last-aligned)
        position — decoder-style attention.
    :param bwd_impl: ``"flash"`` (hand-tiled dq + dk/dv Pallas sweeps,
        O(block²) memory) or ``"reference"`` (XLA autodiff through the dense
        oracle — materializes [T, T] in the backward; kept for debugging and
        as the numerics oracle).
    :param kv_lengths: optional per-example valid key counts [B] (int) —
        keys at or past ``kv_lengths[b]`` are masked out for example ``b``
        (ragged NGram windows padded to a common T). With ``causal``, the
        causal alignment still uses the STATIC T_q/T_kv shapes.
    :param segment_ids: optional int ids for PACKED batches (see
        ``jax_utils.packing``): positions only attend within their own
        segment. Either one [B, T] array (self-attention — requires
        ``T_q == T_kv``) or a ``(q_ids [B, Tq], kv_ids [B, Tkv])`` pair
        (cross-length, e.g. the flash ring's per-block ids). Mutually
        exclusive with ``kv_lengths`` (give padded slots a unique id
        instead). Composes with ``causal``.

    Grouped-query attention (GQA/MQA): ``k``/``v`` may carry FEWER heads
    than ``q`` (``h % h_kv == 0``; ``h_kv == 1`` is multi-query) — each
    group of ``h // h_kv`` query heads attends to one shared K/V head,
    equivalent to repeating K/V heads but without materializing the
    repeat: the kernels' K/V fetches are group-mapped in the BlockSpec
    index maps, so HBM traffic and residual memory scale with ``h_kv``,
    and dK/dV come back group-summed at the K/V head count (f32
    accumulation). Not supported with ``bwd_impl="reference"``.
    """
    _check_bwd_impl(bwd_impl)
    _check_gqa_heads(q, k, v, bwd_impl)
    block_q, block_k = _default_blocks(k.shape[1], block_q, block_k)
    if segment_ids is not None:
        if kv_lengths is not None:
            raise ValueError(
                "segment_ids and kv_lengths are mutually exclusive: give "
                "padded slots their own segment id instead")
        _check_segment_ids(segment_ids, q.shape[1], k.shape[1])
        return _flash_aux(q, k, v, segment_ids, block_q, block_k,
                          interpret, causal, bwd_impl, "segs")
    if kv_lengths is None:
        return _flash_static(q, k, v, block_q, block_k, interpret, causal,
                             bwd_impl)
    return _flash_aux(q, k, v, kv_lengths, block_q, block_k, interpret,
                      causal, bwd_impl, "lens")


def _check_bwd_impl(bwd_impl):
    if bwd_impl not in ("flash", "reference"):
        raise ValueError(
            f"bwd_impl {bwd_impl!r} is not 'flash' or 'reference'")


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_static(q, k, v, block_q, block_k, interpret, causal, bwd_impl):
    if interpret is None:
        interpret = _should_interpret()
    return _flash_forward(q, k, v, block_q, block_k, interpret, causal)


def _fwd(q, k, v, block_q, block_k, interpret, causal, bwd_impl,
         kv_lengths=None, segment_ids=None):
    if interpret is None:
        interpret = _should_interpret()
    if bwd_impl == "reference":
        out = _flash_forward(q, k, v, block_q, block_k, interpret, causal,
                             kv_lengths=kv_lengths, segment_ids=segment_ids)
        return out, (q, k, v, None, None)
    out_padded, lse = _flash_forward(q, k, v, block_q, block_k, interpret,
                                     causal, return_residuals=True,
                                     kv_lengths=kv_lengths,
                                     segment_ids=segment_ids)
    b, t_q, h, _ = q.shape
    out = _from_bh(out_padded[:, :t_q], b, h)
    # o is saved PADDED in [B·H, T, D] form: the backward consumes it block
    # by block in exactly this layout, so nothing is re-transposed there.
    return out, (q, k, v, out_padded, lse)


def _bwd(block_q, block_k, interpret, causal, bwd_impl, residuals, g,
         kv_lengths=None, segment_ids=None):
    if interpret is None:
        interpret = _should_interpret()
    q, k, v, o_padded, lse = residuals
    if bwd_impl == "reference":
        # Recompute-through-the-oracle backward: XLA materializes the [T, T]
        # scores inside its fused backward. Correctness oracle only.
        _, vjp = jax.vjp(
            functools.partial(_attention_reference, causal=causal), q, k, v)
        return vjp(g)
    return _flash_backward(q, k, v, o_padded, lse, g, block_q, block_k,
                           interpret, causal, kv_lengths=kv_lengths,
                           segment_ids=segment_ids)


def _static_fwd(q, k, v, block_q, block_k, interpret, causal, bwd_impl):
    return _fwd(q, k, v, block_q, block_k, interpret, causal, bwd_impl)


def _static_bwd(block_q, block_k, interpret, causal, bwd_impl, residuals, g):
    return _bwd(block_q, block_k, interpret, causal, bwd_impl, residuals, g)


_flash_static.defvjp(_static_fwd, _static_bwd)


# One custom_vjp serves both integer-aux variants (per-example kv_lengths
# and packed-batch segment_ids): the wrappers differ only in which keyword
# the aux array threads through, so ``aux_kind`` selects it statically.
_AUX_KW = {"lens": "kv_lengths", "segs": "segment_ids"}


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash_aux(q, k, v, aux, block_q, block_k, interpret, causal,
               bwd_impl, aux_kind):
    if interpret is None:
        interpret = _should_interpret()
    return _flash_forward(q, k, v, block_q, block_k, interpret, causal,
                          **{_AUX_KW[aux_kind]: aux})


def _aux_fwd(q, k, v, aux, block_q, block_k, interpret, causal, bwd_impl,
             aux_kind):
    if bwd_impl == "reference":
        raise NotImplementedError(
            f"bwd_impl='reference' does not support {_AUX_KW[aux_kind]}; "
            "the dense oracle lives in "
            "models.sequence_model.attention_reference")
    out, residuals = _fwd(q, k, v, block_q, block_k, interpret, causal,
                          bwd_impl, **{_AUX_KW[aux_kind]: aux})
    return out, residuals + (aux,)


def _aux_bwd(block_q, block_k, interpret, causal, bwd_impl, aux_kind,
             residuals, g):
    aux = residuals[-1]
    dq, dk, dv = _bwd(block_q, block_k, interpret, causal, bwd_impl,
                      residuals[:-1], g, **{_AUX_KW[aux_kind]: aux})
    # Integer aux carries no gradient: float0 zeros (handles the
    # (q_ids, kv_ids) pair form of segment_ids too).
    return dq, dk, dv, _int_aux_zeros(aux)


_flash_aux.defvjp(_aux_fwd, _aux_bwd)


def flash_attention_with_lse(q, k, v, block_q=None, block_k=None,
                             interpret=None, causal=False, causal_shift=0,
                             kv_lengths=None, segment_ids=None):
    """Flash attention that ALSO returns the per-row log-sum-exp — the
    merge statistic for combining partial attention over K/V shards
    (ring/blockwise attention: two normalized partials with lse's combine
    exactly into attention over their union).

    Returns ``(out [B, Tq, H, D], lse [B, Tq, H] f32)`` with
    ``lse = -inf`` for rows with no valid key (the true logsumexp of an
    empty set — an empty partial contributes zero weight to a merge).
    Differentiable in BOTH outputs: the backward kernels fold the lse
    cotangent into ds (∂lse/∂s = p). ``causal_shift=-1`` gives STRICT
    causal (key strictly before query) — the striped-ring blocks whose key
    shard sits after the query shard in the interleaved global order.
    ``segment_ids`` may be one [B, T] array (self-attention over a packed
    batch) or a ``(q_ids, kv_ids)`` pair (the ring: the resident K/V block
    carries its own ids); mutually exclusive with ``kv_lengths``.
    """
    _check_gqa_heads(q, k, v)
    block_q, block_k = _default_blocks(k.shape[1], block_q, block_k)
    if segment_ids is not None:
        if kv_lengths is not None:
            raise ValueError(
                "segment_ids and kv_lengths are mutually exclusive: give "
                "padded slots their own segment id instead")
        _check_segment_ids(segment_ids, q.shape[1], k.shape[1])
    return _flash_with_lse(q, k, v, kv_lengths, segment_ids, block_q,
                           block_k, interpret, causal, causal_shift)


def _lse_to_public(lse_raw, b, h, t_q):
    """[B·H, Tq_pad] residual → [B, Tq, H] public lse; the kernel's +inf
    no-valid-key convention flips to -inf (empty-set logsumexp)."""
    lse = lse_raw[:, :t_q]
    lse = jnp.where(jnp.isposinf(lse), -jnp.inf, lse)
    return lse.reshape(b, h, t_q).transpose(0, 2, 1)


def _dlse_to_bh(dlse, tq_p):
    """[B, Tq, H] cotangent → [B·H, Tq_pad] kernel layout (zero-padded)."""
    b, t_q, h = dlse.shape
    flat = dlse.astype(jnp.float32).transpose(0, 2, 1).reshape(b * h, t_q)
    pad = tq_p - t_q
    if pad:
        flat = jnp.pad(flat, ((0, 0), (0, pad)))
    return flat


def _int_aux_zeros(aux):
    """float0 zero cotangent matching an integer aux pytree (or None)."""
    import numpy as np

    return jax.tree_util.tree_map(
        lambda a: np.zeros(jnp.shape(a), dtype=jax.dtypes.float0), aux)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _flash_with_lse(q, k, v, kv_lengths, segment_ids, block_q, block_k,
                    interpret, causal, causal_shift):
    out, _, lse_pub = _with_lse_primal(q, k, v, kv_lengths, segment_ids,
                                       block_q, block_k, interpret, causal,
                                       causal_shift)
    return out, lse_pub


def _with_lse_primal(q, k, v, kv_lengths, segment_ids, block_q, block_k,
                     interpret, causal, causal_shift):
    if interpret is None:
        interpret = _should_interpret()
    out_padded, lse_raw = _flash_forward(
        q, k, v, block_q, block_k, interpret, causal,
        return_residuals=True, kv_lengths=kv_lengths,
        segment_ids=segment_ids, causal_shift=causal_shift)
    b, t_q, h, _ = q.shape
    out = _from_bh(out_padded[:, :t_q], b, h)
    return out, (out_padded, lse_raw), _lse_to_public(lse_raw, b, h, t_q)


def _with_lse_fwd(q, k, v, kv_lengths, segment_ids, block_q, block_k,
                  interpret, causal, causal_shift):
    out, (out_padded, lse_raw), lse_pub = _with_lse_primal(
        q, k, v, kv_lengths, segment_ids, block_q, block_k, interpret,
        causal, causal_shift)
    return (out, lse_pub), (q, k, v, out_padded, lse_raw, kv_lengths,
                            segment_ids)


def _with_lse_bwd(block_q, block_k, interpret, causal, causal_shift,
                  residuals, cotangents):
    if interpret is None:
        interpret = _should_interpret()
    q, k, v, o_padded, lse_raw, kv_lengths, segment_ids = residuals
    do, dlse = cotangents
    dlse_bh = _dlse_to_bh(dlse, lse_raw.shape[1])
    dq, dk, dv = _flash_backward(q, k, v, o_padded, lse_raw, do, block_q,
                                 block_k, interpret, causal,
                                 kv_lengths=kv_lengths,
                                 segment_ids=segment_ids,
                                 causal_shift=causal_shift, dlse=dlse_bh)
    return (dq, dk, dv, _int_aux_zeros(kv_lengths),
            _int_aux_zeros(segment_ids))


_flash_with_lse.defvjp(_with_lse_fwd, _with_lse_bwd)
