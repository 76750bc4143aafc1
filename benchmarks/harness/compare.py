"""The comparisons that decide ``correct``: plain arithmetic on readings of
the program and of the reference, with no knowledge of either."""

import statistics


def order_lags(delivered, reference):
    """How far each delivered item lies from its place in the reference
    order: each takes the earliest unmatched equal item of ``reference``,
    and its lag is the distance between the two positions (the
    reference's length where none is left)."""
    slots = {}
    for j, item in enumerate(reference):
        slots.setdefault(item, []).append(j)
    taken = {item: 0 for item in slots}
    lags = []
    for i, item in enumerate(delivered):
        if taken.get(item, 0) >= len(slots.get(item, ())):
            lags.append(len(reference))
            continue
        lags.append(abs(i - slots[item][taken[item]]))
        taken[item] += 1
    return lags


def once_violations(delivered, reference, epoch):
    """Rows not delivered once per epoch. The k-th delivery of an item is
    matched to its k-th place in the reference (``order_lags``): a row
    group delivered twice pushes its later deliveries an epoch late, one
    lost pulls them an epoch early and leaves a place untaken. Counted:
    deliveries that lag by more than half an ``epoch`` (reader threads
    finish a few places out of turn, never that many), and places more
    than half an epoch before the end that no delivery took."""
    lags = order_lags(delivered, reference)
    late = sum(lag > epoch // 2 for lag in lags)
    taken = {}
    for item in delivered:
        taken[item] = taken.get(item, 0) + 1
    seen, untaken = {}, 0
    for item in reference[:max(0, len(delivered) - epoch // 2)]:
        seen[item] = seen.get(item, 0) + 1
        untaken += seen[item] > taken.get(item, 0)
    return late + untaken


def order_readings(delivered, reference, epoch):
    """``order_mean_lag``: the mean lag, a few items where reader threads
    finish out of turn, about a third of an ``epoch`` in another seed's
    order. ``rows_once_violations``: see :func:`once_violations`."""
    lags = order_lags(delivered, reference)
    return {"order_mean_lag": sum(lags) / len(lags),
            "rows_once_violations": once_violations(delivered, reference,
                                                    epoch)}


def order_faults(reference, other_seed_order, n, epoch):
    """The order numbers of planted faults over the first ``n`` items:
    another seed's order (``order_mean_lag``), and the smaller reading of
    a row group delivered twice and of one lost (``rows_once_violations``)."""
    twice = reference[:n // 2] + reference[n // 2 - 1:n - 1]
    lost = reference[:n // 2] + reference[n // 2 + 1:n + 1]
    return {"order_mean_lag": order_readings(other_seed_order[:n], reference,
                                             epoch)["order_mean_lag"],
            "rows_once_violations": min(once_violations(f, reference, epoch)
                                        for f in (twice, lost))}


def loss_gap(program, reference):
    """Largest relative gap between the program's and the reference's loss
    over the compared steps."""
    return max(abs(p - r) / abs(r) for p, r in zip(program, reference))


def leaf_gap(program, reference, keep):
    """Worst leaf's gap between the program's norm and the reference's,
    over the larger of that leaf's reference norm and the median leaf's.
    ``keep`` names the leaves that count (see ``moving_leaves``)."""
    median = statistics.median(reference[k] for k in keep)
    return max(abs(program[k] - reference[k]) / max(reference[k], median)
               for k in keep)


def moving_leaves(grad_norms, share=1e-3):
    """Leaves whose reference gradient is not nought to rounding: at least
    ``share`` of the median leaf's gradient norm. Leaves below it move by
    round-off alone under any optimizer."""
    median = statistics.median(grad_norms.values())
    return [k for k, v in grad_norms.items() if v >= share * median]


def training_gaps(prog, ref, learning_rate):
    """``prog`` and ``ref`` hold ``losses`` (first steps), ``change1`` and
    ``change3`` (per-leaf norm of the parameters' change after one and
    after three steps); ``ref`` also ``grad1`` (the first gradient's norm
    per leaf). The program's first gradient, as plain SGD applied it, is
    its change after one step over the learning rate."""
    keep = moving_leaves(ref["grad1"])
    grad1 = {k: v / learning_rate for k, v in prog["change1"].items()}
    return {"loss_gap": loss_gap(prog["losses"], ref["losses"]),
            "grad1_gap": leaf_gap(grad1, ref["grad1"], keep),
            "change3_gap": leaf_gap(prog["change3"], ref["change3"], keep)}


def flat_leaves(tree, prefix=""):
    """``{"a/b": leaf}`` for a nested dict of leaves."""
    out = {}
    for k, v in tree.items():
        name = f"{prefix}{k}"
        if isinstance(v, dict):
            out.update(flat_leaves(v, name + "/"))
        else:
            out[name] = v
    return out
