"""The fixture family's half that touches the program."""
TOP_KEYS = {"embed": "gpt.embed_tokens.weight", "norm_f": "gpt.ln_f.weight",
            "lm_head": "gpt.lm_head.weight"}
BLOCK_KEYS = {"norm1": "ln1.weight", "qkv": "attn.qkv_proj.weight",
              "out": "attn.out_proj.weight", "norm2": "ln2.weight",
              "gate_up": "mlp.gate_up.weight", "down": "mlp.down.weight"}


def state_key(name, layer):
    """Leaves are named a layer each, so `layer` is always None."""
    if name in TOP_KEYS:
        return TOP_KEYS[name]
    index, kind = name[1:].split(".")
    return f"gpt.blocks.{index}.{BLOCK_KEYS[kind]}"


def build_model(cfg):
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    return GPTForCausalLM(GPTConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        intermediate_size=cfg["intermediate_size"],
        max_position=cfg["max_position_embeddings"], dropout=0.0,
        use_rope=True, use_rms_norm=True, use_swiglu=True,
        tie_embeddings=False))
