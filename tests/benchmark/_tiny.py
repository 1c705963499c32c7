"""Tiny sizes for the benchmark's CPU tests: the real files, shrunk."""
import copy
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

BENCH_DIR = os.path.join(ROOT, "benchmarks")


def load_json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def bench():
    return load_json("BENCHMARK.json")


def family(model_type="gpt2", root=ROOT):
    from benchmarks.harness.job import load_family

    return load_family(root, model_type)


def tiny_cfg(name="gpt2-medium", **over):
    cfg = load_json("benchmarks", "configs", f"{name}.json")
    cfg.update(vocab_size=257, n_positions=128, n_embd=64, n_layer=2, n_head=4)
    cfg.update(over)
    return cfg


def tiny_large_cfg(**over):
    """The second configuration at a tiny size of its own: another width,
    depth and number of heads than `tiny_cfg()`."""
    return tiny_cfg("gpt2-large", **dict(dict(n_embd=80, n_layer=3, n_head=5),
                                         **over))


def tiny_train_mix(**over):
    mix = load_json("benchmarks", "traffic", "train-s1024.json")
    mix.update(batch=4, seq=32, warmup_seconds=0.05)
    mix.update(over)
    return mix


def tiny_serve_mix(**over):
    mix = copy.deepcopy(load_json("benchmarks", "traffic", "serve-chat.json"))
    mix.update(rate_per_s=8.0, max_total=128, check_requests=4,
               preroll_seconds=0.3,
               warm={"requests": 2, "prompt": {"dist": "fixed", "value": 20},
                     "output": {"dist": "fixed", "value": 5}},
               prompt={"dist": "lognormal", "median": 24, "sigma": 0.7,
                       "min": 4, "max": 80},
               output={"dist": "lognormal", "median": 10, "sigma": 0.6,
                       "min": 4, "max": 24})
    mix["geometry"].update(max_slots=4, block_size=8, num_blocks=64,
                           max_seq_len=128, prefill_chunk=16, decode_steps=4,
                           max_new_tokens=24, decode_kernel="xla")
    mix.update(over)
    return mix


def make_job(workload, cfg, mix, limits, *, seed=7, seconds=1.0, trace=False):
    import time

    from benchmarks.harness.job import Job

    return Job(root=ROOT, bench=bench(), workload=workload, cfg=cfg, mix=mix,
               limits=limits, seed=seed, seconds=seconds, trace=trace,
               t0=time.perf_counter())
