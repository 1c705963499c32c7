"""The harness's module that touches the program (`paddle_tpu`): it hands the
model that the configuration's family builds (`families/<model_type>/
program.py`) the benchmark's weights, and wraps the train step and the
server. Everything measured or compared lives in the other modules and never
imports the program."""
import math

import jax
import jax.numpy as jnp

from . import weights as W


PART_BYTES = 2 ** 30    # of the checkpoint alive beside the model's leaves


def state_parts(family, cfg, seed, *, round_to=None, out_dtype="float32"):
    """The benchmark's weights as the program keeps them, a part at a time:
    yields {state_dict key: value} for the family's leaves in sorted order,
    as many a part as stay under PART_BYTES (one leaf at least), a leaf that
    is stacked over layers as its layers. The whole tree is never made."""
    model = family.model
    stacked = {entry[0] for i in range(len(model.layer_kinds(cfg)))
               for entry in model.layer_leaves(cfg, i).values()
               if not isinstance(entry, str)}
    size = jnp.dtype(out_dtype).itemsize
    parts, room = [], 0
    for name, (shape, _, _) in sorted(model.leaves(cfg).items()):
        nbytes = size * math.prod(shape)
        if not parts or nbytes > room:
            parts.append({})
            room = PART_BYTES
        room -= nbytes
        for i in (range(shape[0]) if name in stacked else (None,)):
            parts[-1][name if i is None else (name, i)] = (
                family.program.state_key(name, i))
    for keys in parts:
        drawn = W.make_weights(family, cfg, seed, only=list(keys),
                               round_to=round_to, out_dtype=out_dtype)
        yield {keys[entry]: value for entry, value in drawn.items()}


def load_weights(family, model, cfg, seed, *, serve):
    """Replace the model's parameters by the benchmark's, made from the seed
    on the device part by part: bfloat16 for serving (the checkpoint's own
    values), float32 masters for training. Beside the model's own leaves at
    most one part is alive."""
    state = model.state_dict()
    todo = set(state)
    kind = (dict(round_to="bfloat16", out_dtype="bfloat16") if serve else {})
    for part in state_parts(family, cfg, seed, **kind):
        for key, value in part.items():
            if key not in todo:
                raise KeyError(f"the benchmark's leaf {key!r} is none of the "
                               f"model's, or came twice")
            if tuple(state[key].shape) != tuple(value.shape):
                raise ValueError(f"{key}: model {tuple(state[key].shape)} "
                                 f"against {tuple(value.shape)}")
            state[key]._value = value
            todo.remove(key)
    if todo:
        raise KeyError(f"leaves of the model that the benchmark did not "
                       f"make: {sorted(todo)[:6]}")


def ordered_leaves(family, cfg, by_key):
    """Values of a {state_dict key: value} map in the family's `leaf_names`
    order, fused projections split into their parts."""
    return [family.model.parts(
        kind, by_key[family.program.state_key(kind, layer)])[part]
        for kind, part, layer in family.model.leaf_names(cfg)]


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

    def __init__(self, family, cfg, opt, seed):
        import paddle_tpu as paddle
        from paddle_tpu.jit.train import TrainStep
        from paddle_tpu.nn.clip import ClipGradByGlobalNorm

        self.paddle, self.family, self.cfg, self.opt = paddle, family, cfg, opt
        self.model = family.program.build_model(cfg)
        load_weights(family, self.model, cfg, seed, serve=False)
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
        return (_norms(ordered_leaves(self.family, self.cfg, m1))
                / (1 - self.opt["beta1"]))

    def parameters_now(self):
        return ordered_leaves(self.family, self.cfg, {
            k: t._value for k, t in self.model.state_dict().items()})

    def delta_norms(self, start):
        return _delta_norms(self.parameters_now(), start)


def start_leaves(family, cfg, seed):
    """The parameters a Trainer of this seed starts from, made again from
    the seed (its own are donated away by the first step)."""
    by_key = {}
    for part in state_parts(family, cfg, seed):
        by_key.update(part)
    return ordered_leaves(family, cfg, by_key)


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

    def __init__(self, family, cfg, geometry, seed):
        from paddle_tpu.inference.scheduler import (
            ContinuousGenerateBatchingPredictor,
        )

        self.cfg = cfg
        self.model = family.program.build_model(cfg)
        self.model.eval()
        load_weights(family, self.model, cfg, seed, serve=True)
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
