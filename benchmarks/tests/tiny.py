"""Tiny sizes for CPU runs of the harness (never used on the chip)."""

TINY = {
    "imagenet1k": {"rows": 128, "image_pool": 16, "image_height": 40,
                   "image_width": 56, "rows_per_row_group": 8,
                   "batch_per_chip": 8, "crop": 16, "conv_features": 4,
                   "hidden": 32, "num_classes": 10, "reader_workers": 1},
    "criteo1tb": {"rows": 8192, "rows_per_row_group": 512,
                  "batch_per_chip": 128, "num_embeddings_per_feature": 1024,
                  "embed_dim": 8, "bottom_mlp_widths": [16, 8],
                  "top_mlp_widths": [16, 1], "max_ind_range": 100000,
                  "reader_workers": 1,
                  # The full model's loss falls from ~0.8 to ~0.2 in three
                  # steps at 0.01; the tiny one learns that fast at 0.3.
                  "learning_rate": 0.3},
}


def cell(name):
    """The cell ``BENCHMARK.json`` names ``name``; for a ``config.traffic``
    pair it does not hold (``criteo1tb.local``), one built alike."""
    import json
    import os

    from harness import cell as cells
    from harness.main import load_benchmark, load_workload

    if any(w["name"] == name for w in load_benchmark()["workloads"]):
        return load_workload(name)
    config, traffic = name.split(".")
    with open(os.path.join(cells.BENCH_DIR, "traffic", f"{traffic}.json")) as f:
        return dict(json.load(f), name=name, config=config, traffic=traffic,
                    chips=1)
