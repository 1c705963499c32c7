"""The one module that touches the program (`paddle_tpu`): it builds the
system under test from a configuration file, hands it the benchmark's
weights, and reads its counters. Everything measured or compared lives in
the other modules and never imports the program."""
import jax
import jax.numpy as jnp

from . import weights as W

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


@jax.jit
def _unstack(tree):
    out = {}
    for kind, value in tree.items():
        if kind in TOP_KEYS:
            out[TOP_KEYS[kind]] = value
        else:
            for i in range(value.shape[0]):
                out[state_key(kind, i)] = value[i]
    return out


def load_weights(model, cfg, seed, *, serve):
    """Replace the model's parameters by the benchmark's, made from the seed
    on the device: bfloat16 for serving (the checkpoint's own values), float32
    masters for training."""
    tree = (W.make_weights(cfg, seed, round_to="bfloat16",
                           out_dtype="bfloat16") if serve
            else W.make_weights(cfg, seed))
    flat = _unstack(tree)
    state = model.state_dict()
    if set(state) != set(flat):
        raise KeyError(f"the model's leaves are not the benchmark's: "
                       f"{sorted(set(state) ^ set(flat))[:6]}")
    for key, tensor in state.items():
        if tuple(tensor.shape) != tuple(flat[key].shape):
            raise ValueError(f"{key}: model {tuple(tensor.shape)} against "
                             f"{tuple(flat[key].shape)}")
        tensor._value = flat[key]


def ordered_leaves(cfg, by_key):
    """Values of a {state_dict key: value} map in `weights.leaf_names`
    order, fused projections split into their parts."""
    return [W.parts(kind, by_key[state_key(kind, layer)])[part]
            for kind, part, layer in W.leaf_names(cfg)]


@jax.jit
def _norms(leaves):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in leaves])


@jax.jit
def _delta_norms(leaves, start):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(
        a.astype(jnp.float32) - b.astype(jnp.float32))))
        for a, b in zip(leaves, start)])


class Trainer:
    """The compiled train step with its state: `jit.train.TrainStep` on the
    model, AdamW after clipping by the global norm, float32 masters, the
    forward and backward traced under `amp.auto_cast(level="O2")` so every
    product runs in bfloat16. ONE object: set-up drives its first steps and
    hands it to the window."""

    def __init__(self, cfg, opt, seed):
        import paddle_tpu as paddle
        from paddle_tpu.jit.train import TrainStep
        from paddle_tpu.nn.clip import ClipGradByGlobalNorm

        self.paddle, self.cfg, self.opt = paddle, cfg, opt
        self.model = build_model(cfg)
        load_weights(self.model, cfg, seed, serve=False)
        self.optimizer = paddle.optimizer.AdamW(
            learning_rate=opt["learning_rate"], beta1=opt["beta1"],
            beta2=opt["beta2"], epsilon=opt["epsilon"],
            weight_decay=opt["weight_decay"],
            parameters=self.model.parameters(),
            grad_clip=ClipGradByGlobalNorm(opt["clip_norm"]))
        self.step_fn = TrainStep(self.model, lambda logits, loss: loss,
                                 self.optimizer)
        self._keys = {id(t): k for k, t in self.model.state_dict().items()}

    def to_device(self, ids, labels):
        return self.paddle.to_tensor(ids), self.paddle.to_tensor(labels)

    def step(self, x, y):
        """The window's own call. Returns the loss, still on the device."""
        with self.paddle.amp.auto_cast(level="O2", dtype="bfloat16"):
            return self.step_fn(x, labels=y)._value

    def compiles(self):
        return self.step_fn._jitted._cache_size()

    def first_gradient_norms(self):
        """Per leaf, the norm of the gradient as AdamW got it in step 1,
        worked out from the first moment after that step:
        m1 = (1 - beta1) * g."""
        m1 = {self._keys[pid]: v for pid, v in
              self.optimizer._accumulators["moment1"].items()}
        return _norms(ordered_leaves(self.cfg, m1)) / (1 - self.opt["beta1"])

    def parameters_now(self):
        return ordered_leaves(
            self.cfg, {k: t._value for k, t in self.model.state_dict().items()})

    def delta_norms(self, start):
        return _delta_norms(self.parameters_now(), start)


def start_leaves(cfg, seed):
    """The parameters a Trainer of this seed starts from, made again from
    the seed (its own are donated away by the first step)."""
    return ordered_leaves(cfg, _unstack(W.make_weights(cfg, seed)))


def memory_peak_bytes():
    """Peak bytes in use on the fullest chip."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks))


class Server:
    """The continuous scheduler on the paged pool, in process: bfloat16
    weights and pool, AOT warm-up of its step programs, greedy decoding.
    `geometry` is the mix's: max_slots, block_size, num_blocks, max_seq_len,
    prefill_chunk, decode_steps, spec_k, max_new_tokens, decode_kernel,
    prefix_cache."""

    def __init__(self, cfg, geometry, seed):
        from paddle_tpu.inference.scheduler import (
            ContinuousGenerateBatchingPredictor,
        )

        self.cfg = cfg
        self.model = build_model(cfg)
        self.model.eval()
        load_weights(self.model, cfg, seed, serve=True)
        self.pred = ContinuousGenerateBatchingPredictor(
            self.model, warmup=True, **geometry)

    def wait_ready(self, timeout):
        import time

        deadline = time.monotonic() + timeout
        while not self.pred.ready():
            if time.monotonic() > deadline:
                raise TimeoutError(f"the server was not ready in {timeout}s")
            time.sleep(0.05)
        stats = self.pred.warm_stats()
        if self.pred.warm_errors() or stats is None or stats["missing"]:
            raise RuntimeError(f"AOT warm-up incomplete: stats={stats} "
                               f"errors={self.pred.warm_errors()}")
        return stats

    def stream(self, prompt, max_new, timeout):
        """The timed entry: an iterator of arrays of new tokens."""
        return self.pred.infer_stream(prompt, timeout=timeout,
                                      max_new_tokens=max_new)

    def compiles_after_ready(self):
        """Step programs built after the warm-up armed its sentinel."""
        counter = self.pred._recompile_counter
        return sum(int(counter.labels(self.pred._component, prog).value)
                   for prog in ("prefill_chunk", "decode_step", "verify_step"))

    def close(self):
        self.pred.close()
