"""The `dots3_note` family's half that touches the program (`paddle_tpu`):
the model the program builds for a configuration of this family, and where
the program keeps each of the benchmark's leaves."""

TOP_KEYS = {"embed": "model.embed", "norm_f": "model.norm_f",
            "lm_head": "model.lm_head"}
# leaves the program keeps inside a sublayer of the block
NESTED = {"router": "experts", "router_bias": "experts",
          "experts_gate_up": "experts", "experts_down": "experts"}
OF_THE_BLOCK = ("norm1", "norm2", "mlp_gate_up", "mlp_down", "shared_gate_up",
                "shared_down")


def state_key(leaf, layer):
    """Leaves are named a layer each (`L3.q_a`); `layer` is always None."""
    if leaf in TOP_KEYS:
        return TOP_KEYS[leaf]
    index, name = leaf[1:].split(".", 1)
    where = ("" if name in OF_THE_BLOCK
             else NESTED.get(name, "attn") + ".")
    return f"model.layers.{index}.{where}{name}"


def build_model(cfg):
    """Dots3ForCausalLM at the file's sizes, its leaves ABSTRACT and in the
    serving precision: the harness fills them part by part, and no second
    set of parameters is ever made (4.09 G of them do not fit twice)."""
    import paddle_tpu as paddle
    from paddle_tpu.models.dots3 import Dots3Config, Dots3ForCausalLM

    with paddle.LazyGuard():
        return Dots3ForCausalLM(Dots3Config(**dict(cfg, dtype="bfloat16")))
