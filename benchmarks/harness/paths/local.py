"""The local path: the configuration's reader in the trainer's process,
into ``make_jax_dataloader`` with the loader's default prefetch."""

import random

from harness.paths import Source


def open_source(cfg, data, sz, seed):
    from petastorm_tpu.jax_utils import make_jax_dataloader

    stage = cfg.device_stage(sz, seed)
    reader = cfg.make_reader(data, sz, seed)
    loader = make_jax_dataloader(reader, sz["batch_per_chip"],
                                 device_stage=stage)
    return Source(loader, close=lambda: (reader.stop(), reader.join()))


def ref_order(seed, groups, epochs):
    """Row groups in the order the seed gives them, epoch after epoch: one
    ``random.Random(seed)`` shuffles the ordered list anew each epoch (the
    reader's ventilation order; its threads may finish a few out of turn)."""
    rnd, out = random.Random(seed), []
    for _ in range(epochs):
        order = list(range(groups))
        rnd.shuffle(order)
        out.extend(order)
    return out
