"""The operation and byte counts of the consumer steps and the device input
work, against counts made by hand at the cells' own sizes."""

from harness import cell as cells


def test_imagenet1k_step_flops():
    cfg = cells.load_config("imagenet1k")
    sz = cfg.load_sizes()
    # Per image, forward: conv 224*224 outputs * 64 features * 27 MACs;
    # dense1 802,816 x 2,048; dense2 2,048 x 1,000. Backward: two times each
    # forward (input and weight gradients), less the conv's input gradient.
    conv = 2 * 224 * 224 * 64 * 27
    dense1 = 2 * 802_816 * 2_048
    dense2 = 2 * 2_048 * 1_000
    per_image = 3 * (dense1 + dense2) + 2 * conv
    assert per_image == 10_224_107_520        # ~10.2 GFLOP
    assert cfg.step_flops(sz, 128) == 128 * per_image


def test_imagenet1k_input_bytes():
    cfg = cells.load_config("imagenet1k")
    sz = cfg.load_sizes()
    # Read 128 raw 375x500x3 uint8 images, write 128 crops 224x224x3 bf16.
    assert cfg.input_bytes(sz, 128) == 128 * 375 * 500 * 3 + 128 * 224 * 224 * 3 * 2


def test_criteo1tb_step_flops():
    cfg = cells.load_config("criteo1tb")
    sz = cfg.load_sizes()
    # Per row, forward: bottom 13x512 and 512x128; interaction of 27
    # vectors of 128 (27*27 dot products); top (351+128)x1024 and 1024x1.
    bottom1 = 2 * 13 * 512
    rest = 2 * 512 * 128 + 2 * 27 * 27 * 128 + 2 * 479 * 1024 + 2 * 1024
    assert cfg.step_flops(sz, 8192) == 8192 * (2 * bottom1 + 3 * rest)
    assert cfg.input_bytes(sz, 8192) is None


def test_criteo1tb_tables_fit_one_chip():
    cfg = cells.load_config("criteo1tb")
    sz = cfg.load_sizes()
    tables = sz["num_sparse"] * sz["num_embeddings_per_feature"] * sz["embed_dim"] * 4
    assert tables == 3_489_660_928       # 3.49 GB of float32 tables
