"""What decides `correct` on the chip, checked here at a tiny size: the plain
float32 reference agrees with the program (forward logits; loss, gradients
and the AdamW update; prefill then decode through the paged path), the fp8
control reads far above the program, and a run whose timed path is broken
underneath comes out not correct — once for each fault a cell can have."""
import json

import numpy as np
import pytest

import _tiny
from benchmarks import run as bench_run
from benchmarks.harness import (compare, lastline, program, reference,
                                serve_driver, traffic, train_driver, weights)

DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}
TRAIN_CELL = {"name": "gpt2-medium-train-s1024", "config": "gpt2-medium",
              "traffic": "train-s1024", "chips": 1}
SERVE_CELL = {"name": "gpt2-large-serve-chat", "config": "gpt2-large",
              "traffic": "serve-chat", "chips": 1}


# Gaps scale with the model: a 2-layer model of width 64 reads a larger loss
# gap in bfloat16 than the cells do, so these tests hold the same numbers to
# limits read at THIS size on the CPU (sound runs: loss 2.6e-4..5e-4, gradient
# 0.002..0.005, change 0.010..0.013; the served gap is 0), in the same place
# between the sound readings and the broken ones. The cells' own limits, and
# the chip's readings they were set from, are in benchmarks/limits/ and PERF.md.
TINY_LIMITS = {
    "gpt2-medium-train-s1024": {"loss_gap": 2e-3, "grad_norm_gap": 0.008,
                                "delta_norm_gap": 0.04},
    "gpt2-large-serve-chat": {"served_logit_gap": 1e-3,
                              "wrong_token_counts": 0},
}


def limits_of(cell):
    cells = compare.load_limits(
        f"{_tiny.BENCH_DIR}/limits/{cell['name']}.json")
    tiny = TINY_LIMITS[cell["name"]]
    assert set(tiny) == set(cells)      # the same numbers, other limits
    return tiny


def finish(job, outcome):
    """The rest of a run after the look for a chip: judge and build the line."""
    outcome.memory_peak_bytes = outcome.memory_peak_bytes or 1
    return bench_run.finish(job, outcome, DEVICE)


# ------------------------------------------------------------------ control
def test_fp8_rounding_is_the_float8_e4m3_grid():
    import jax.numpy as jnp

    x = np.concatenate([
        100 * np.random.default_rng(0).standard_normal(50_000),
        [448, -448, 0.0, 2.0 ** -6, 2.0 ** -9, 1.1 * 2.0 ** -10, 1e-5, 0.0019,
         17.0, 18.0, 19.0, 0.4375, 0.46875]]).astype(np.float32)
    x = x[np.abs(x) <= 448]
    want = np.asarray(jnp.asarray(x).astype(jnp.float8_e4m3fn), np.float32)
    assert np.array_equal(np.asarray(reference.round_e4m3(x)), want)


# ------------------------------------------------------------------ forward
def test_reference_forward_agrees_with_the_program():
    import paddle_tpu as paddle

    cfg = _tiny.tiny_cfg(activation_function="gelu")    # the program's erf
    model = program.build_model(cfg)
    model.eval()
    program.load_weights(model, cfg, seed=3, serve=False)
    ids = np.random.default_rng(0).integers(0, cfg["vocab_size"], (2, 48))
    got = np.asarray(model(paddle.to_tensor(ids))._value)
    params = weights.make_weights(cfg, 3)
    want = np.asarray(reference.logits_fn(cfg, params, ids))
    assert np.max(np.abs(got - want)) < 2e-4
    # the source's tanh form differs from the program's erf form by less
    # than a bfloat16 step of the logits
    tanh = np.asarray(reference.logits_fn(
        dict(cfg, activation_function="gelu_new"), params, ids))
    assert 0 < np.max(np.abs(tanh - want)) < 5e-3


def test_weights_are_the_seeds_and_the_served_checkpoint_is_their_bf16_image():
    cfg = _tiny.tiny_cfg()
    a = weights.make_weights(cfg, 2_147_483_659)
    b = weights.make_weights(cfg, 2_147_483_659)
    c = weights.make_weights(cfg, 2_147_483_660)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["wte"], c["wte"])
    served = weights.make_weights(cfg, 5, round_to="bfloat16",
                                  out_dtype="bfloat16")
    image = weights.make_weights(cfg, 5, round_to="bfloat16")
    assert str(served["qkv_w"].dtype) == "bfloat16"
    assert all(np.array_equal(np.asarray(served[k], np.float32), image[k])
               for k in image)
    assert len(weights.leaf_names(cfg)) == 4 + cfg["n_layer"] * 16


# -------------------------------------------------------------------- train
@pytest.fixture(scope="module")
def train_job():
    return _tiny.make_job(TRAIN_CELL, _tiny.tiny_cfg(), _tiny.tiny_train_mix(),
                          limits_of(TRAIN_CELL), seed=11, seconds=0.3)


@pytest.fixture(scope="module")
def sound_train(train_job):
    return train_driver.run(train_job)


def test_sound_train_run_is_correct(train_job, sound_train):
    line = finish(train_job, sound_train)
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert set(line["compared"]) == {"loss_gap", "grad_norm_gap",
                                     "delta_norm_gap"}
    assert line["metrics"]["train_tokens_per_s"]["value"] > 0


def test_fp8_control_reads_above_the_program(train_job, sound_train):
    fed, seen = sound_train.records["first_steps"]
    ref = train_driver.reference_numbers(train_job, fed)
    control = compare.train_numbers(
        train_driver.reference_numbers(train_job, fed, quant=True), ref)
    correct, _ = compare.judge(control, train_job.limits)
    assert not correct
    assert control["grad_norm_gap"] >= 3 * sound_train.numbers["grad_norm_gap"]


class StateUnchanged(program.Trainer):
    """A step that returns its state unchanged: the update moves nothing."""

    def __init__(self, cfg, opt, seed):
        super().__init__(cfg, dict(opt, learning_rate=0.0), seed)
        self.opt = opt


class HalfTheBatch(program.Trainer):
    """Half of the batch left out, the mean taken over the rest."""

    def to_device(self, ids, labels):
        half = ids.shape[0] // 2
        return super().to_device(ids[:half], labels[:half])


@pytest.mark.parametrize("broken,number", [
    (StateUnchanged, "delta_norm_gap"), (HalfTheBatch, "grad_norm_gap")])
def test_broken_train_step_is_not_correct(train_job, broken, number):
    outcome = train_driver.run(train_job, make_trainer=broken)
    line = finish(train_job, outcome)
    assert line["correct"] is False
    got = line["compared"][number]
    assert got["value"] > got["limit"]
    if broken is StateUnchanged:
        assert got["value"] == pytest.approx(1.0)   # no leaf moved


# -------------------------------------------------------------------- serve
@pytest.fixture(scope="module")
def serve_job():
    return _tiny.make_job(SERVE_CELL, _tiny.tiny_cfg("gpt2-large"),
                          _tiny.tiny_serve_mix(), limits_of(SERVE_CELL),
                          seed=13, seconds=1.0)


def test_sound_serve_run_is_correct_and_the_control_reads_above_it(serve_job):
    outcome = serve_driver.run(serve_job)
    line = finish(serve_job, outcome)
    assert line["correct"] is True, line["compared"]
    sent = traffic.open_loop_requests(serve_job.mix, serve_job.cfg,
                                       serve_job.seed, 1.0)
    # the lead-in's requests are sent and not counted
    assert line["attempted"] == sum(1 for r in sent if r.due_s >= 0) < len(sent)
    assert line["failed"] == 0
    assert set(line["metrics"]) == set(lastline.expected_metrics(
        serve_job.bench, SERVE_CELL["name"], trace=False)) < set(
            outcome.metrics)    # the driver offers more than the cell reports
    assert json.loads(json.dumps(line)) == line
    # the control in the program's place: at the same positions of the same
    # prompts and tokens, the token that fp8 puts first lies far below
    both = serve_driver.served_logit_gaps(
        serve_job.cfg, serve_job.seed, outcome.records["answers"],
        control=True)
    assert both["served_logit_gap"] == outcome.numbers["served_logit_gap"]
    assert both["control_logit_gap"] >= 3 * both["served_logit_gap"]
    assert not compare.judge(
        {"served_logit_gap": both["control_logit_gap"],
         "wrong_token_counts": 0}, serve_job.limits)[0]


class AlteredToken(program.Server):
    """A token altered where it is produced: the third token of every answer
    is another one, and decoding goes on from what the server made."""

    def stream(self, prompt, max_new, timeout):
        seen = 0
        for chunk in super().stream(prompt, max_new, timeout):
            chunk = np.array(chunk)
            if seen <= 2 < seen + len(chunk):
                chunk[2 - seen] = (chunk[2 - seen] + 101) % self.cfg["vocab_size"]
            seen += len(chunk)
            yield chunk


class ShortAnswer(program.Server):
    """An answer that says the wrong thing: one token short."""

    def stream(self, prompt, max_new, timeout):
        return super().stream(prompt, max_new - 1, timeout)


@pytest.mark.parametrize("broken,number", [
    (AlteredToken, "served_logit_gap"), (ShortAnswer, "wrong_token_counts")])
def test_broken_server_is_not_correct(serve_job, broken, number):
    outcome = serve_driver.run(serve_job, make_server=broken)
    line = finish(serve_job, outcome)
    assert line["correct"] is False
    got = line["compared"][number]
    assert got["value"] > got["limit"]
