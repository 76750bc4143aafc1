"""Loader layer: the benchmark's own span around ``next(loader)``, mean per
step of the traced window."""


def read(run):
    spans = run.spans["loader.next"]
    return 1000.0 * sum(spans) / len(spans) if spans else None
