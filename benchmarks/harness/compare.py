"""The comparison that decides `correct`: numbers read off the timed path
against the plain reference's, each held to a limit of its own. The limits
are data (`benchmarks/limits/<workload>.json`), set from readings on the chip
that PERF.md lists."""
import json
import sys

import numpy as np

# a leaf whose reference gradient is under this share of the median leaf's
# is nought to rounding (a key's bias under softmax): Adam moves it by
# round-off alone, so its change is not compared
ZERO_GRADIENT_SHARE = 1e-3


def worst_leaf_gap(program, reference):
    """Largest gap between the program's norm of a leaf and the reference's
    (a gap of norms, not the norm of a difference), measured against the
    reference's norm of that leaf or of the median leaf, whichever is larger."""
    program = np.asarray(program, float)
    reference = np.asarray(reference, float)
    scale = np.maximum(reference, np.median(reference))
    return float(np.max(np.abs(program - reference) / scale))


def train_numbers(program, reference):
    """`program` / `reference`: {"losses": [k], "grad_norms": [leaves],
    "delta_norms": [leaves]} of the first steps."""
    lp = np.asarray(program["losses"], float)
    lr = np.asarray(reference["losses"], float)
    ref_grad = np.asarray(reference["grad_norms"], float)
    moved = ref_grad >= ZERO_GRADIENT_SHARE * np.median(ref_grad)
    return {
        "loss_gap": float(np.max(np.abs(lp - lr) / np.abs(lr))),
        "grad_norm_gap": worst_leaf_gap(program["grad_norms"], ref_grad),
        "delta_norm_gap": worst_leaf_gap(
            np.asarray(program["delta_norms"])[moved],
            np.asarray(reference["delta_norms"])[moved]),
    }


def load_limits(path):
    with open(path) as f:
        return {k: v["limit"] for k, v in json.load(f)["numbers"].items()}


def judge(numbers, limits, out=sys.stderr):
    """Hold each number to its limit. Returns (correct, compared) and prints
    each number beside its limit, one a line. A number without a limit, a
    limit without a number, or a number that is not finite fails."""
    compared, correct = {}, True
    for name in sorted(set(numbers) | set(limits)):
        value, limit = numbers.get(name), limits.get(name)
        ok = (value is not None and limit is not None
              and np.isfinite(value) and value <= limit)
        correct = correct and bool(ok)
        compared[name] = {"value": value, "limit": limit}
        print(f"compared {name}: {value} (limit {limit}) "
              f"{'ok' if ok else 'NOT OK'}", file=out)
    if not compared:
        correct = False
        print("compared nothing: not correct", file=out)
    return correct, compared
