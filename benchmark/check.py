"""The comparisons that decide ``correct``: gaps of norms by the worst
leaf for training, the widest logit gap for serving.  Pure numpy."""
import numpy as np

# leaves whose reference gradient is under this share of the median
# leaf's are nought to rounding (a key's bias under softmax): their
# change is round-off, and is left out of the change comparison
DEAD_GRADIENT = 1e-3


def worst_leaf_gap(program, reference, keep=None):
    """max over leaves of |program norm - reference norm| measured
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger.  Returns (gap, index of the worst leaf)."""
    p, r = np.asarray(program, float), np.asarray(reference, float)
    keep = np.ones(len(r), bool) if keep is None else np.asarray(keep)
    floor = np.median(r[keep])
    gap = np.abs(p - r) / np.maximum(np.maximum(r, floor), 1e-300)
    gap = np.where(keep, gap, 0.0)
    gap = np.where(np.isfinite(p), gap, np.inf)
    i = int(np.argmax(gap))
    return float(gap[i]), i


def live_leaves(reference_grad_norms):
    r = np.asarray(reference_grad_norms, float)
    return r >= DEAD_GRADIENT * np.median(r)


def relative_gap(program, reference):
    if not np.isfinite(program):
        return float("inf")
    return float(abs(program - reference) / abs(reference))


def training_numbers(program, reference):
    """[(name, value)] of a training cell from the two sides' readings:
    dicts with ``losses`` [3], ``grad_norms`` [leaves], ``change_norms``
    [leaves] in the same leaf order."""
    out = [(f"loss_step{i + 1}_gap",
            relative_gap(program["losses"][i], reference["losses"][i]))
           for i in range(len(reference["losses"]))]
    g, gi = worst_leaf_gap(program["grad_norms"], reference["grad_norms"])
    live = live_leaves(reference["grad_norms"])
    c, ci = worst_leaf_gap(program["change_norms"],
                           reference["change_norms"], live)
    out += [("grad_norm_gap", g), ("change_norm_gap", c)]
    return out, {"grad_leaf": gi, "change_leaf": ci,
                 "dead_leaves": int((~live).sum())}


def widest_logit_gap(best, took):
    """The widest gap by which a served token's reference logit lies
    below the reference's best, over every served position."""
    gaps = np.asarray(best, float) - np.asarray(took, float)
    if not np.isfinite(gaps).all():
        return float("inf")
    return float(gaps.max()) if len(gaps) else float("inf")
