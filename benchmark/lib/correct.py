"""The comparison that decides ``correct``: what the timed trainer
produced in its first three minibatches against the plain reference, and
the rows every window epoch fed."""

from __future__ import annotations

import statistics

import numpy as np

#: leaves whose reference gradient is under this share of the median
#: leaf's are left out of the change comparison (they move by round-off)
QUIET_LEAF = 1e-3


def _flat(tree) -> list:
    """The leaves of a list, one entry a layer, of None or a tuple of
    any number of leaves (a leaf may be None), in order."""
    return [v for layer in tree if layer is not None for v in layer
            if v is not None]


def _names(tree) -> list[str]:
    """``<parameterised layer>.<leaf>`` for each leaf of ``_flat``."""
    layers = [la for la in tree if la is not None]
    return [f"{k}.{j}" for k, la in enumerate(layers)
            for j, v in enumerate(la) if v is not None]


def _worst_gap(prog: list[float], ref: list[float], keep=None) -> float:
    """Largest gap between the program's and the reference's norm of a
    leaf, against the reference's norm of that leaf or of the median
    leaf, whichever is larger."""
    if len(prog) != len(ref):
        return float("inf")
    med = statistics.median(ref)
    gaps = [abs(p - r) / max(r, med) for i, (p, r) in
            enumerate(zip(prog, ref)) if keep is None or keep[i]]
    return max(gaps) if gaps else float("inf")


def _diff_norms(prog: list, ref: list) -> list[float]:
    """Norm of each leaf's difference, estimated from its two sketches."""
    return [float(np.linalg.norm(np.subtract(p, r)))
            for p, r in zip(prog, ref)]


def first_steps_numbers(first: dict, ref: dict, output_leaf: int) -> dict:
    """``first``: the probe's reading of the program; ``ref``: what
    ``reference.follow`` returned for the same rows; ``output_leaf``:
    the model file's ``output_leaf(cfg)``, a place among the leaves in
    order.

    ``out_grad_diff`` is the control's number: the norm of the
    difference itself (from the two sketches) of the first gradient of
    that leaf (the output layer's weights, which operand rounding
    reaches through one product) against the reference's norm of it.
    Deeper leaves sum terms that cancel, so the stated precision already
    reads a tenth there and the control only three to four times that."""
    loss_gap = max(abs(p - r) / abs(r)
                   for p, r in zip(first["losses"], ref["losses"]))
    ref_g, prog_g = _flat(ref["grad_norms"]), _flat(first["grad_norms"])
    med = statistics.median(ref_g)
    keep = [g >= QUIET_LEAF * med for g in ref_g]
    v0 = max(_flat(first.get("velocity0_norms", [])), default=0.0)
    diffs = _diff_norms(_flat(first["grad_sketches"]),
                        _flat(ref["grad_sketches"]))
    out = int(output_leaf)
    return {
        "loss_gap": loss_gap if v0 == 0.0 else float("inf"),
        "grad_norm_gap": _worst_gap(prog_g, ref_g),
        "change_norm_gap": _worst_gap(_flat(first["change_norms"]),
                                      _flat(ref["change_norms"]), keep),
        "out_grad_diff": (diffs[out] / ref_g[out]
                          if len(diffs) == len(ref_g) == len(prog_g)
                          and 0 <= out < len(ref_g) else float("inf")),
    }


def leaf_table(first: dict, ref: dict) -> list[str]:
    """One line a leaf, for a person who studies a limit: the
    reference's norms, the program's gaps of norms and, of the first
    gradient, its norm of the difference, by the measures above."""
    rg, rc = _flat(ref["grad_norms"]), _flat(ref["change_norms"])
    pg, pc = _flat(first["grad_norms"]), _flat(first["change_norms"])
    dg = _diff_norms(_flat(first["grad_sketches"]),
                     _flat(ref["grad_sketches"]))
    if not len(rg) == len(rc) == len(pg) == len(pc) == len(dg):
        return [f"leaves differ: {len(rg)} {len(rc)} {len(pg)} {len(pc)}"]
    mg, mc = statistics.median(rg), statistics.median(rc)
    names = _names(ref["grad_norms"])
    return [f"leaf {names[i]}: ref grad {a:.4g} gap "
            f"{abs(b - a) / max(a, mg):.3g} diff {e / max(a, mg):.3g}"
            f" | ref change {c:.4g} gap {abs(d - c) / max(c, mc):.3g}"
            + ("" if a >= QUIET_LEAF * mg else " (quiet)")
            for i, (a, b, c, d, e) in enumerate(zip(rg, pg, rc, pc, dg))]


def rows_misfed(calls: list[dict], train_range: tuple[int, int],
                valid_range: tuple[int, int]) -> int:
    """Over the run's calls: training rows of an epoch not fed exactly
    once (head call plus the deferred tail, for every epoch that has
    both), and validation rows not evaluated exactly once a pass.  0 in
    a sound run; a window in which nothing could be checked counts as
    one row."""
    lo, hi = train_range
    by_epoch: dict = {}
    bad = checked = 0
    for c in calls:
        idx = np.asarray(c["indices"])
        if c["kind"] == "train":
            by_epoch.setdefault(c["epoch"], []).append(idx)
        elif len(idx) and valid_range[0] <= idx[0] < valid_range[1]:
            want = np.arange(*valid_range)
            bad += int(len(idx) != len(want)
                       or (np.sort(idx) != want).any()) * len(want)
    for parts in by_epoch.values():
        fed = np.concatenate(parts)
        if len(fed) < hi - lo:
            continue                    # an epoch the window cut
        counts = np.bincount(fed - lo, minlength=hi - lo) \
            if fed.min() >= lo and fed.max() < hi else np.zeros(hi - lo)
        bad += int((counts != 1).sum())
        checked += 1
    return bad if checked else max(bad, 1)


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """Every number beside its limit; ``correct`` only if each number
    that has a limit is finite and within it."""
    checks, ok = {}, True
    for name, value in numbers.items():
        limit = limits.get(name)
        checks[name] = {"value": value, "limit": limit}
        if limit is not None and not (np.isfinite(value)
                                      and value <= limit):
            ok = False
    for name in limits:
        if name not in numbers:
            checks[name] = {"value": None, "limit": limits[name]}
            ok = False
    return ok, checks
