"""One general generator for every traffic mix. A mix is a data file under
`benchmarks/traffic/`; its `kind` says how load is offered:

  train        a job: `batch` packed rows of `seq` tokens a step, fresh token
               ids each step.
  open_loop    requests arrive on a schedule, whatever the server does:
               `rate_per_s`, exponential gaps (Poisson arrivals).

Every seed gets the SAME set of sizes and gaps: lengths and gaps are the
mix's distributions at evenly spaced quantiles (a stratified sample), so two
seeds never differ in the amount of work. The seed draws the ORDER of the
lengths and of the gaps, the token ids and the weights. `preroll_seconds` of
the same traffic come before an open-loop window opens, so that it opens on
a server in its steady state. A length spec is {"dist": "lognormal",
"median", "sigma", "min", "max"} or {"dist": "fixed", "value"}."""
import dataclasses
import json
import math
import statistics

import numpy as np

KINDS = ("train", "open_loop")


def load_mix(path):
    with open(path) as f:
        mix = json.load(f)
    if mix.get("kind") not in KINDS:
        raise ValueError(f"{path}: kind {mix.get('kind')!r} is none of {KINDS}")
    return mix


def rng_for(seed, stream):
    """Independent streams of one seed: 0 the order of the lengths, 1 token
    ids, 2 the sample of requests that is compared, 3 the order of the gaps."""
    return np.random.default_rng([int(seed), int(stream)])


def quantile_lengths(spec, n):
    """n whole lengths at the quantiles (i + 0.5) / n of the distribution,
    clipped to [min, max]: the same multiset for every seed."""
    u = (np.arange(n) + 0.5) / n
    if spec["dist"] == "fixed":
        return np.full(n, int(spec["value"]), np.int64)
    if spec["dist"] == "lognormal":
        z = np.array([statistics.NormalDist().inv_cdf(v) for v in u])
        x = spec["median"] * np.exp(spec["sigma"] * z)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def exponential_gaps(rate_per_s, n):
    """n gaps at the exponential distribution's evenly spaced quantiles,
    scaled so that they sum to n / rate exactly."""
    u = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-u)
    return gaps * (n / rate_per_s / gaps.sum())


@dataclasses.dataclass
class Request:
    index: int
    due_s: float            # open loop: offset from the window's start
    prompt: np.ndarray      # int64 token ids
    max_new: int


def requests_due(mix, cfg, seed, due):
    """One request for each due time: the mix's lengths in the seed's order,
    token ids from the seed."""
    n = len(due)
    order = rng_for(seed, 0)
    plens = order.permutation(quantile_lengths(mix["prompt"], n))
    outs = order.permutation(quantile_lengths(mix["output"], n))
    outs = np.minimum(outs, mix["max_total"] - plens)   # prompt + output
    if (outs < 1).any():
        raise ValueError("a prompt leaves no room for an output token")
    ids = rng_for(seed, 1)
    return [Request(i, float(due[i]),
                    ids.integers(0, cfg["vocab_size"], int(plens[i]),
                                 dtype=np.int64), int(outs[i]))
            for i in range(n)]


def open_loop_requests(mix, cfg, seed, seconds):
    """Every request due in [-preroll_seconds, seconds); the window is
    [0, seconds) and a request with a negative due time is its lead-in."""
    preroll = mix.get("preroll_seconds", 0.0)
    n = max(1, int(round(mix["rate_per_s"] * (preroll + seconds))))
    gaps = rng_for(seed, 3).permutation(
        exponential_gaps(mix["rate_per_s"], n))
    due = np.cumsum(gaps) - gaps[0] / 2 - preroll    # the first one at once
    return requests_due(mix, cfg, seed, due)


def train_batches(mix, cfg, seed):
    """Endless (ids, labels) of a packed causal job: labels are the next
    token. Rows all differ: every id is drawn anew."""
    rng = rng_for(seed, 1)
    while True:
        tokens = rng.integers(0, cfg["vocab_size"],
                              (mix["batch"], mix["seq"] + 1), dtype=np.int64)
        yield tokens[:, :-1], tokens[:, 1:]


def percentile(values, q):
    """The q-th percentile (0..100), linear between ranks; every value
    counts."""
    xs = sorted(values)
    if not xs:
        raise ValueError("no value to take a percentile of")
    k = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)
