"""Accelerator-side decode/augment stage — closing the decode ceiling.

BENCH r03–r05 showed the trainer pipeline decode-bound: the host finishes a
batch's pixel work (cast to model dtype, normalize, crop, flip) barely
faster than the device consumes it, and staging float32 pixels moves 4x the
bytes of the stored uint8. This module inverts the boundary the way tf.data
attacks it with fused vectorized transforms and cedar attacks it by choosing
*where* each operator runs: the loader stages the RAW uint8 batch (bytes,
not pixels) and a single JIT-compiled fused kernel performs
crop + flip + cast + normalize ON the accelerator, with the raw input
buffer DONATED to the kernel so HBM for in-flight raw batches is bounded
and the runtime may reuse it in place.

The stage is pluggable behind two seams:

- :meth:`DeviceStage.split` — which fields of a collated batch are raw
  image bytes (staged raw, decoded on device) vs ordinary tensors (staged
  as before). Entropy-coded formats (JPEG/PNG bitstreams) have no pure-JAX
  decode, so that half of "decode" stays host-side in the reader's codec —
  behind this same interface, exactly as the issue allows — while
  everything after the entropy decode (the per-pixel arithmetic, which is
  where the float32 bytes and the host multiply-adds were) fuses on-device.
- :meth:`DeviceStage.apply` — the fused kernel itself. Augment randomness
  is derived ONLY from (seed, step ordinal, field ordinal) through
  ``jax.random.fold_in``, so an epoch's augment sequence is reproducible
  across runs and invariant to prefetch depth, staging thread placement,
  and device count; the step ordinal is a traced scalar so one compiled
  program serves every step.

``host_reference`` mirrors the kernel with numpy (same PRNG draws, same
operation order), so CPU-backend parity tests can assert bit-exact
cast/normalize output and exact crop/flip selections.

HBM accounting (see ``docs/guides/device_decode.md``): with the stage
armed, a loader keeps at most ``device_prefetch`` decoded batches plus one
in-flight raw batch alive; the raw buffer is donated to the kernel on
backends that implement donation (TPU/GPU), and dropped by the loader as
soon as the decoded output exists.
"""

from __future__ import annotations

import numpy as np

__all__ = ["DeviceStage"]


def _as_channel_array(value, dtype):
    """mean/std broadcast shape: scalar or per-channel [C] → [1,1,1,C]-able."""
    arr = np.asarray(value, dtype=dtype)
    if arr.ndim > 1:
        raise ValueError("normalize mean/std must be scalars or 1-D "
                         f"per-channel sequences, got shape {arr.shape}")
    return arr


#: Input dtypes whose every value bf16 holds exactly: only these are
#: cropped by one-hot selection. Any other dtype would be rounded to 8
#: significant bits, and a NaN or Inf would spread along its row and
#: column (0·NaN is NaN), so those batches are sliced per image.
_BF16_EXACT = (np.dtype(np.uint8), np.dtype(np.int8), np.dtype(np.bool_))


def batch_in_tiles(layout):
    """Whether a ``[B, ...]`` array's device ``layout`` (``arr.format.layout``)
    tiles the batch: B is one of the two most minor dimensions, which a TPU
    tiles (8, 128). It puts a uint8 batch there when the other dimensions
    fit its tiles worse, as for ``[128, 375, 500, 3]``. A per-image crop
    then loops over the batch, touching one lane of 128 or one sublane of
    8 a trip, and the stage crops by :func:`_select_crop` instead. A batch
    on a major dimension (always, in a CPU's row-major layouts), or no
    layout known, keeps the per-image slice, which is then the faster."""
    return layout is not None and 0 in layout.major_to_minor[-2:]


def _select_crop(x, offsets, flips, crop):
    """Crop ``[B, H, W, C]`` images of a ``_BF16_EXACT`` dtype at
    per-image ``offsets`` (and mirror those whose ``flips`` bit is set) by
    two batched one-hot matmuls: rows ``[B, ch, H]``, then columns
    ``[B, cw, W]`` whose source column runs backwards for a flipped image.

    Every op is the same across the batch, so a batch in the tiles
    (:func:`batch_in_tiles`) costs no loop. Exact: each output is one
    product of an 8-bit value (exact in bf16) with 1.0, summed with zeros.
    Costs 2·B·ch·W·C·(H + cw) FLOPs; on a TPU the casts fuse into the
    matmuls, and the temporaries are at most one relaid-out copy of the
    raw batch.
    """
    import jax.numpy as jnp

    ch, cw = crop
    _, h, w, _ = x.shape
    rows = offsets[:, :1] + jnp.arange(ch)                       # [B, ch]
    cols = jnp.arange(cw)
    if flips is not None:
        cols = jnp.where(flips[:, None], cw - 1 - cols, cols)
    cols = offsets[:, 1:] + cols                                 # [B, cw]
    row_sel = (rows[:, :, None] == jnp.arange(h)).astype(jnp.bfloat16)
    col_sel = (cols[:, :, None] == jnp.arange(w)).astype(jnp.bfloat16)
    x = jnp.einsum("bih,bhwc->biwc", row_sel, x.astype(jnp.bfloat16),
                   preferred_element_type=jnp.bfloat16)
    return jnp.einsum("bjw,biwc->bijc", col_sel, x,
                      preferred_element_type=jnp.bfloat16)


class DeviceStage:
    """Fused on-device decode/augment: uint8 bytes in, model-dtype pixels out.

    :param image_fields: field names to treat as raw image batches. ``None``
        (default) infers them: uint8 arrays of rank >= 3 after collation
        (``[B, H, W, C]``-shaped codec output).
    :param output_dtype: dtype the kernel casts to on device (default
        float32; bfloat16 works and halves decoded HBM).
    :param normalize: ``None`` or ``(mean, std)`` — scalars or per-channel
        sequences; applied as ``(x - mean) * (1 / std)`` with the
        reciprocal precomputed once in numpy so the device and the host
        reference multiply by bit-identical constants.
    :param crop: ``None`` or ``(height, width)`` — a per-image random crop
        (uniform offsets), applied before the normalize so the sliced-away
        pixels are never normalized. A uint8, int8 or bool batch whose
        device layout tiles the batch (:func:`batch_in_tiles`, a TPU's
        layout for most image sizes) is cropped (and flipped) by an exact
        one-hot selection that reads the whole raw batch as bf16; any other
        batch is sliced per image.
    :param flip: random horizontal flip per image (p=0.5).
    :param seed: PRNG seed for crop offsets / flip bits.
    :param donate: donate the raw input buffers to the kernel. ``None``
        (default) enables donation only on backends that implement it
        (TPU/GPU) — CPU donation is a no-op that warns.
    """

    def __init__(self, image_fields=None, output_dtype=np.float32,
                 normalize=None, crop=None, flip=False, seed=0,
                 donate=None):
        self._image_fields = (None if image_fields is None
                              else tuple(image_fields))
        self._dtype = np.dtype(output_dtype)
        if normalize is not None:
            mean, std = normalize
            self._mean = _as_channel_array(mean, self._dtype)
            std_arr = _as_channel_array(std, self._dtype)
            if np.any(std_arr == 0):
                raise ValueError("normalize std must be non-zero")
            # ONE reciprocal, computed host-side: the kernel and the host
            # reference both multiply by this exact value, keeping the
            # parity contract bit-exact (a device-side divide could round
            # differently).
            self._inv_std = (np.asarray(1.0, self._dtype)
                             / std_arr).astype(self._dtype)
        else:
            self._mean = self._inv_std = None
        if crop is not None:
            crop = (int(crop[0]), int(crop[1]))
            if crop[0] < 1 or crop[1] < 1:
                raise ValueError(f"crop must be positive, got {crop}")
        self._crop = crop
        self._flip = bool(flip)
        self._seed = int(seed)
        self._donate = donate
        self._jitted = None  # built lazily (first apply) — no jax import cost
        #: Cumulative raw bytes handed to the H2D path through this stage —
        #: the uint8-vs-float32 staging ledger benchmarks report as
        #: ``h2d_bytes_per_image``.
        self.h2d_bytes = 0

    # -- field routing -----------------------------------------------------

    def is_image_field(self, name, arr):
        if self._image_fields is not None:
            return name in self._image_fields
        return arr.dtype == np.uint8 and arr.ndim >= 3

    def split(self, batch):
        """Partition a collated host batch into (raw image fields, rest)."""
        raw, rest, object_fields = {}, {}, []
        for name, col in batch.items():
            arr = np.asarray(col)
            if arr.dtype == object:
                # Never stageable, even when named explicitly — but the
                # error below must say "wrong dtype", not "absent".
                object_fields.append(name)
                rest[name] = col
            elif self.is_image_field(name, arr):
                raw[name] = arr
            else:
                rest[name] = col
        if self._image_fields is not None:
            wrong_dtype = [f for f in self._image_fields
                           if f in object_fields]
            if wrong_dtype:
                raise TypeError(
                    f"device stage image_fields {wrong_dtype} collated to "
                    f"object dtype (ragged or undecoded rows?) — the "
                    f"on-device kernel needs dense same-shape arrays; "
                    f"decode/shape them in the reader (codec or "
                    f"TransformSpec) first")
            missing = [f for f in self._image_fields if f not in raw]
            if missing:
                raise KeyError(
                    f"device stage image_fields {missing} absent from the "
                    f"batch (fields: {sorted(batch)})")
        return raw, rest

    # -- the fused kernel --------------------------------------------------

    def _field_key(self, step, index):
        """Augment randomness root for (step ordinal, field ordinal) —
        shared verbatim by the kernel and the host reference."""
        import jax

        key = jax.random.fold_in(jax.random.PRNGKey(self._seed), step)
        return jax.random.fold_in(key, index)

    def _augment(self, x, key, backend, select=False):
        """crop → flip → cast → normalize, identical draw structure on both
        backends; ``backend`` is the jnp module on device, numpy on host.
        ``select`` crops an 8-bit batch by one-hot selection (device only)."""
        import jax

        jnp = backend
        offsets = flips = None
        if self._crop is not None:
            if x.ndim != 4:
                raise ValueError(
                    f"crop expects [B, H, W, C] batches, got rank {x.ndim}")
            ch, cw = self._crop
            b, h, w = x.shape[0], x.shape[1], x.shape[2]
            if ch > h or cw > w:
                raise ValueError(f"crop {self._crop} larger than image "
                                 f"({h}, {w})")
            key, crop_key = jax.random.split(key)
            offsets = jax.random.randint(
                crop_key, (b, 2), 0,
                jnp.asarray([h - ch + 1, w - cw + 1]))
        if self._flip:
            key, flip_key = jax.random.split(key)
            flips = jax.random.bernoulli(flip_key, 0.5, (x.shape[0],))
        if offsets is not None and select and x.dtype in _BF16_EXACT:
            # One selection does both the crop and the flip.
            x = _select_crop(x, offsets, flips, self._crop)
        else:
            if offsets is not None and backend is np:
                offsets = np.asarray(offsets)
                x = np.stack([img[o[0]:o[0] + ch, o[1]:o[1] + cw]
                              for img, o in zip(x, offsets)])
            elif offsets is not None:
                def crop_one(img, off):
                    return jax.lax.dynamic_slice(
                        img, (off[0], off[1], 0), (ch, cw, img.shape[2]))

                x = jax.vmap(crop_one)(x, offsets)
            if flips is not None:
                if backend is np:
                    flips = np.asarray(flips)
                # Horizontal = the width axis: second-to-last for
                # channel-last [B, H, W, C] batches, last for channelless
                # [B, H, W].
                flipped = jnp.flip(x, axis=x.ndim - 2 if x.ndim >= 4
                                   else x.ndim - 1)
                x = jnp.where(
                    jnp.reshape(flips, (x.shape[0],) + (1,) * (x.ndim - 1)),
                    flipped, x)
        x = x.astype(self._dtype)
        if self._mean is not None:
            x = (x - self._mean) * self._inv_std
        return x

    def _kernel(self, raw, step, select=frozenset()):
        import jax.numpy as jnp

        return {name: self._augment(raw[name], self._field_key(step, i), jnp,
                                    name in select)
                for i, name in enumerate(sorted(raw))}

    def _build_jit(self, input_platform=None):
        import jax

        donate = self._donate
        if donate is None:
            # CPU's donation path is unimplemented (jax warns and copies);
            # the point of donation is bounding accelerator HBM. Decide
            # from the platform the inputs are actually committed to — the
            # loader may stage onto a non-default device (e.g. a CPU mesh
            # on a GPU/TPU host).
            platform = input_platform or jax.local_devices()[0].platform
            donate = platform in ("tpu", "gpu")
        self._jitted = jax.jit(self._kernel, static_argnames=("select",),
                               donate_argnums=(0,) if donate else ())

    def apply(self, raw_device, step):
        """Run the fused kernel over already-staged raw arrays.

        ``step`` is the batch's production ordinal: it only seeds the
        augment PRNG (traced, so every step shares one compiled program).
        The raw buffers are donated on TPU/GPU — callers must not touch
        them afterwards.
        """
        if not raw_device:
            return {}
        if self._jitted is None:
            first = next(iter(raw_device.values()))
            devices = getattr(first, "devices", None)
            platform = None
            if callable(devices):
                devs = devices()
                if devs:
                    platform = next(iter(devs)).platform
            self._build_jit(platform)
        # The crop follows each field's device layout; a numpy input has
        # none yet, and is sliced.
        select = frozenset(
            name for name, arr in raw_device.items()
            if batch_in_tiles(getattr(getattr(arr, "format", None), "layout",
                                      None)))
        return self._jitted(dict(raw_device), np.int32(step), select=select)

    # -- host parity reference --------------------------------------------

    def host_reference(self, raw, step):
        """Numpy mirror of :meth:`apply` for parity tests: same PRNG draws
        (jax.random on host), same operation order, same precomputed
        normalization constants — cast/normalize output is bit-exact on
        the CPU backend; crop and flip are exact index selections."""
        return {name: self._augment(np.asarray(raw[name]),
                                    self._field_key(step, i), np)
                for i, name in enumerate(sorted(raw))}

    def describe(self):
        """Static stage configuration as pure data — what the pipeline
        graph embeds in its ``device_decode`` node (and an autotune
        decision trail records once), so a profile snapshot names the
        kernel it measured (``docs/guides/pipeline.md``)."""
        return {
            "image_fields": (list(self._image_fields)
                             if self._image_fields is not None else None),
            "output_dtype": self._dtype.name,
            "normalize": self._mean is not None,
            "crop": self._crop,
            "flip": self._flip,
            "seed": self._seed,
        }

    def __repr__(self):
        return (f"DeviceStage(image_fields={self._image_fields}, "
                f"output_dtype={self._dtype.name}, "
                f"normalize={self._mean is not None}, crop={self._crop}, "
                f"flip={self._flip}, seed={self._seed})")
