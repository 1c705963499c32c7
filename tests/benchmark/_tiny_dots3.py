"""The `dots3-note-prev-ep8` configuration at a tiny size for CPU tests: the
real file, shrunk (as `_tiny.py` does for GPT-2), every mechanism kept: a
dense full layer, an expert full layer, an expert window layer."""
import copy

import numpy as np

import _tiny

CELL = "dots3-note-ep8-serve-longdoc"


def tiny_cfg(**over):
    cfg = _tiny.load_json("benchmarks", "configs", "dots3-note-prev-ep8.json")
    cfg.update(
        vocab_size=96, hidden_size=64, num_hidden_layers=3,
        layer_types=["full_attention", "full_attention",
                     "sliding_attention"],
        intermediate_size=96, moe_intermediate_size=32, n_routed_experts=8,
        published_n_routed_experts=8, ep_size=1, ep_rank=0,
        num_experts_per_tok=2, num_attention_heads=4, q_lora_rank=24,
        kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
        v_head_dim=8, index_n_heads=8, index_head_dim=8, index_topk=6,
        swa_num_attention_heads=2, swa_q_lora_rank=24, swa_kv_lora_rank=24,
        swa_qk_nope_head_dim=12, swa_qk_rope_head_dim=4, swa_v_head_dim=8,
        sliding_window_size=5, initializer_range=0.2, router_bias_range=0.1)
    cfg.update(over)
    return cfg


def family():
    return _tiny.family("dots3_note")


def built(cfg, seed, dtype="float32", served=False):
    """(model, tree): the program's model of `cfg` holding the benchmark's
    float32 weights of `seed`, and those weights as the reference takes
    them; with `served` the float32 image of the bfloat16 checkpoint, which
    is what the serve check's reference holds."""
    from benchmarks.harness import weights
    from paddle_tpu.models.dots3 import Dots3Config, Dots3ForCausalLM

    fam = family()
    model = Dots3ForCausalLM(Dots3Config(**dict(cfg, dtype=dtype)))
    model.eval()
    tree = weights.make_weights(fam, cfg, seed,
                                round_to="bfloat16" if served else None)
    state = model.state_dict()
    for name, value in tree.items():
        key = fam.program.state_key(name, None)
        assert tuple(state[key].shape) == tuple(value.shape), key
        state[key]._value = value
    assert len(state) == len(tree)
    return model, tree


def tiny_mix(**over):
    mix = copy.deepcopy(_tiny.load_json("benchmarks", "traffic",
                                        "serve-longdoc.json"))
    mix.update(rate_per_s=8.0, max_total=64, check_requests=3,
               preroll_seconds=0.3,
               warm={"requests": 1, "prompt": {"dist": "fixed", "value": 12},
                     "output": {"dist": "fixed", "value": 3}},
               prompt={"dist": "lognormal", "median": 24, "sigma": 0.4,
                       "min": 10, "max": 44},
               output={"dist": "lognormal", "median": 6, "sigma": 0.5,
                       "min": 3, "max": 12})
    mix["geometry"].update(max_slots=3, block_size=4, num_blocks=32,
                           max_seq_len=64, prefill_chunk=8, decode_steps=4,
                           max_new_tokens=12)
    mix.update(over)
    return mix


def ids(seed, *shape, vocab=96):
    return np.random.default_rng(seed).integers(0, vocab, shape)
