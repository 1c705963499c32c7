#!/usr/bin/env python3
"""What would `correct` make of a fault in the `dots3_note` cell? One run of
`benchmarks/run.py` as it stands (same arguments, same last line); after its
window, the served tokens of the first `--rows` checked requests are read
again against the reference with ONE mechanism altered at a time, through the
same `serve_driver.served_logit_gaps` and the cell's own limits:

    chiprun -- python3 tools/plant_reference_faults.py \
        --workload dots3-note-ep8-serve-longdoc --seed 3000000816 \
        --seconds 30 --trace 0 [--rows 3] [--faults selection_bottom_k,...]

The program is held fixed and the fault is planted in the reference: the gap
(how far the reference's logit of a served token lies below its best) is
symmetric in who holds the fault, and a pass of the reference costs a minute
where a faulty program costs a whole run. Each reading goes to standard error
as `FAULT <name> served_logit_gap <value> (limit <limit>)`.

Faults: `selection_bottom_k` (the `index_topk` keys of SMALLEST indexer score
kept), `selection_off` (every causal key), `window_257`, `window_512` (the
edge one key off), `no_gate` (g = 1), `no_rescale`."""
import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
import benchmarks.run as run      # noqa: E402  T0 is taken here, as in a plain run
from benchmarks.harness import serve_driver  # noqa: E402

DEFAULT = "selection_bottom_k,window_257,no_gate"


def bottom_k(model):
    """The family's `allowed_keys` with the selection turned over."""
    import jax
    import jax.numpy as jnp

    def allowed(cfg, full, index, first, block, seq):
        t = first + jnp.arange(block)[:, None]
        s = jnp.arange(seq)[None, :]
        causal = s <= t
        if not full:
            return causal & (s > t - cfg["sliding_window_size"])
        q, k, w = index
        q = jax.lax.dynamic_slice_in_dim(q, first, block)
        w = jax.lax.dynamic_slice_in_dim(w, first, block)
        dots = jnp.einsum("tjd,sd->jts", q, k, precision=model.HIGHEST)
        score = jnp.einsum("jts,tj->ts", jax.nn.relu(dots), w,
                           precision=model.HIGHEST)
        score = jnp.where(causal, score, jnp.inf)
        topk = cfg["index_topk"]
        if seq <= topk:
            return causal
        kth = jnp.sort(score, axis=-1)[:, topk - 1][:, None]
        return causal & (score <= kth)
    return allowed


def no_gate(p):
    """sigmoid(0) = 1/2 on every head and `o` doubled: g = 1."""
    return dict(p, gate=p["gate"] * 0, o=p["o"] * 2)


def plans(model, cfg, width):
    """{fault: (cfg, allowed_keys or None, change of a layer's leaves or
    None)}."""
    return {
        "selection_bottom_k": (cfg, bottom_k(model), None),
        "selection_off": (dict(cfg, index_topk=width), None, None),
        "window_257": (dict(cfg, sliding_window_size=257), None, None),
        "window_512": (dict(cfg, sliding_window_size=512), None, None),
        "no_gate": (cfg, None, no_gate),
        "no_rescale": (dict(cfg, apply_mla_qkv_lora_rescale=False), None,
                       None)}


def with_faults(real, names, rows, limit):
    def note(name, got):
        print(f"FAULT {name} served_logit_gap {got.get('served_logit_gap')} "
              f"(limit {limit})", file=sys.stderr, flush=True)

    def gaps(family, cfg, seed, answers, width, **kw):
        out = real(family, cfg, seed, answers, width, **kw)
        few = answers[:rows]
        note("none", real(family, cfg, seed, few, width, **kw))
        model = family.model
        keep_allowed, keep_layer = model.allowed_keys, model.layer
        for name in names:
            altered, allowed, change = plans(model, cfg, width)[name]
            model.allowed_keys = allowed or keep_allowed
            if change is not None:
                model.layer = (lambda c, kind, p, x, mm, change=change:
                               keep_layer(c, kind, change(p), x, mm))
            try:
                note(name, real(family, altered, seed, few, width, **kw))
            finally:
                model.allowed_keys, model.layer = keep_allowed, keep_layer
        return out
    return gaps


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=3)
    ap.add_argument("--faults", default=DEFAULT)
    args, rest = ap.parse_known_args(argv)
    workload = rest[rest.index("--workload") + 1]
    limit = run.load_job(workload, 0, 0, 0).limits.get("served_logit_gap")
    serve_driver.served_logit_gaps = with_faults(
        serve_driver.served_logit_gaps, args.faults.split(","), args.rows,
        limit)
    return run.main(rest)


if __name__ == "__main__":
    sys.exit(main())
