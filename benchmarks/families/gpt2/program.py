"""The `gpt2` family's half that touches the program (`paddle_tpu`): the
model the program builds for a configuration of this family, and where the
program keeps each of the benchmark's leaves."""

# the program's state_dict key of each leaf of the benchmark's tree
TOP_KEYS = {"wte": "gpt.embed_tokens.weight",
            "wpe": "gpt.embed_positions.weight",
            "ln_f_w": "gpt.ln_f.weight", "ln_f_b": "gpt.ln_f.bias"}
BLOCK_KEYS = {"ln1_w": "ln1.weight", "ln1_b": "ln1.bias",
              "qkv_w": "attn.qkv_proj.weight", "qkv_b": "attn.qkv_proj.bias",
              "out_w": "attn.out_proj.weight", "out_b": "attn.out_proj.bias",
              "ln2_w": "ln2.weight", "ln2_b": "ln2.bias",
              "fc1_w": "mlp.fc1.weight", "fc1_b": "mlp.fc1.bias",
              "down_w": "mlp.down.weight", "down_b": "mlp.down.bias"}


def state_key(kind, layer):
    """`layer` is None for a leaf that belongs to no layer."""
    if layer is None:
        return TOP_KEYS[kind]
    return f"gpt.blocks.{layer}.{BLOCK_KEYS[kind]}"


def build_model(cfg):
    """GPTForCausalLM with the GPT-2 block, at the file's sizes."""
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    return GPTForCausalLM(GPTConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["n_embd"],
        num_layers=cfg["n_layer"], num_heads=cfg["n_head"],
        intermediate_size=cfg["n_inner"], max_position=cfg["n_positions"],
        dropout=0.0, use_rope=False, use_rms_norm=False, use_swiglu=False,
        tie_embeddings=True))
