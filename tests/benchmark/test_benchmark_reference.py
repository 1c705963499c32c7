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
GPT2 = _tiny.family()


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
    model = GPT2.program.build_model(cfg)
    model.eval()
    program.load_weights(GPT2, model, cfg, seed=3, serve=False)
    ids = np.random.default_rng(0).integers(0, cfg["vocab_size"], (2, 48))
    got = np.asarray(model(paddle.to_tensor(ids))._value)
    params = weights.make_weights(GPT2, cfg, 3)
    want = np.asarray(reference.logits_fn(GPT2, cfg, params, ids))
    assert np.max(np.abs(got - want)) < 2e-4
    # the source's tanh form differs from the program's erf form by less
    # than a bfloat16 step of the logits
    tanh = np.asarray(reference.logits_fn(
        GPT2, dict(cfg, activation_function="gelu_new"), params, ids))
    assert 0 < np.max(np.abs(tanh - want)) < 5e-3


def test_weights_are_the_seeds_and_the_served_checkpoint_is_their_bf16_image():
    cfg = _tiny.tiny_cfg()
    a = weights.make_weights(GPT2, cfg, 2_147_483_659)
    b = weights.make_weights(GPT2, cfg, 2_147_483_659)
    c = weights.make_weights(GPT2, cfg, 2_147_483_660)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["wte"], c["wte"])
    served = weights.make_weights(GPT2, cfg, 5, round_to="bfloat16",
                                  out_dtype="bfloat16")
    image = weights.make_weights(GPT2, cfg, 5, round_to="bfloat16")
    assert str(served["qkv_w"].dtype) == "bfloat16"
    assert all(np.array_equal(np.asarray(served[k], np.float32), image[k])
               for k in image)
    assert len(GPT2.model.leaf_names(cfg)) == 4 + cfg["n_layer"] * 16


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

    def __init__(self, family, cfg, opt, seed):
        super().__init__(family, cfg, dict(opt, learning_rate=0.0), seed)
        self.opt = opt


class HalfTheBatch(program.Trainer):
    """Half of the batch left out, the mean taken over the rest."""

    def to_device(self, ids, labels):
        half = ids.shape[0] // 2
        return super().to_device(ids[:half], labels[:half])


@pytest.fixture(scope="module", params=[
    (StateUnchanged, "delta_norm_gap"), (HalfTheBatch, "grad_norm_gap")],
    ids=lambda p: f"{p[0].__name__}-{p[1]}")
def broken_train(request, train_job):
    """(the fault, the number that has to catch it, the run's outcome)."""
    broken, number = request.param
    return broken, number, train_driver.run(train_job, make_trainer=broken)


def test_broken_train_step_is_not_correct(train_job, broken_train):
    broken, number, outcome = broken_train
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


@pytest.fixture(scope="module")
def sound_serve(serve_job):
    return serve_driver.run(serve_job)


def test_sound_serve_run_is_correct(serve_job, sound_serve):
    outcome = sound_serve
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


def test_the_serve_control_reads_above_the_program(serve_job, sound_serve):
    """The control in the program's place: at the same positions of the same
    prompts and tokens, the token that fp8 puts first lies far below."""
    outcome = sound_serve
    both = serve_driver.served_logit_gaps(
        GPT2, serve_job.cfg, serve_job.seed, outcome.records["answers"],
        serve_job.mix["geometry"]["max_seq_len"], control=True)
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


@pytest.fixture(scope="module", params=[
    (AlteredToken, "served_logit_gap"), (ShortAnswer, "wrong_token_counts")],
    ids=lambda p: f"{p[0].__name__}-{p[1]}")
def broken_serve(request, serve_job):
    broken, number = request.param
    return number, serve_driver.run(serve_job, make_server=broken)


def test_broken_server_is_not_correct(serve_job, broken_serve):
    number, outcome = broken_serve
    line = finish(serve_job, outcome)
    assert line["correct"] is False
    got = line["compared"][number]
    assert got["value"] > got["limit"]


# ------------------------------------------------- the serve check's routes
def greedy_answers(cfg, seed, lengths):
    """(prompt, tokens) as a sound server gives them: each token the
    reference's own first choice, by the whole-image forward."""
    import jax

    width = cfg["n_positions"]
    tree = weights.make_weights(GPT2, cfg, seed, round_to="bfloat16")
    forward = jax.jit(lambda ids: reference.logits_fn(GPT2, cfg, tree, ids))
    rng = np.random.default_rng(seed)
    out = []
    for plen, n in lengths:
        ids = np.zeros((1, width), np.int64)
        ids[0, :plen] = rng.integers(0, cfg["vocab_size"], plen)
        for at in range(plen, plen + n):
            ids[0, at] = int(np.argmax(np.asarray(forward(ids))[0, at - 1]))
        out.append((ids[0, :plen].copy(), [int(t) for t in ids[0, plen:plen + n]]))
    return out


ROUTE_CFG = dict(n_layer=2, vocab_size=1031)
ROUTE_SEED, ROUTE_WIDTH = 31, 128


def route_gaps(answers, hbm_bytes, control):
    return serve_driver.served_logit_gaps(
        GPT2, _tiny.tiny_cfg("gpt2-large", **ROUTE_CFG), ROUTE_SEED, answers,
        ROUTE_WIDTH, most_new=24, control=control, hbm_bytes=hbm_bytes)


@pytest.fixture(scope="module")
def whole_image():
    """(answers, what the whole-image route reads on them, control beside)."""
    cfg = _tiny.tiny_cfg("gpt2-large", **ROUTE_CFG)
    answers = greedy_answers(cfg, ROUTE_SEED, [(20, 9), (7, 30), (100, 28)])
    answers[1][1][4] = (answers[1][1][4] + 1) % cfg["vocab_size"]   # a gap
    return answers, route_gaps(answers, None, True)


def room_needed(cfg):
    """The chip's memory at which the whole-image route, control beside the
    reference, just fits."""
    return (weights.image_bytes(GPT2, cfg) + 2 * GPT2.model.forward_bytes(
        cfg, ROUTE_WIDTH)) / reference.ServeCheck.ROOM


def test_the_serve_checks_route_is_reckoned_from_bytes(whole_image):
    cfg = _tiny.tiny_cfg("gpt2-large", **ROUTE_CFG)

    def fits(hbm_bytes, cfg=cfg, width=ROUTE_WIDTH):
        return reference.ServeCheck(GPT2, cfg, ROUTE_SEED, width, control=True,
                                    hbm_bytes=hbm_bytes).whole_image_fits()
    need = room_needed(cfg)
    assert fits(None) and fits(need + 1) and not fits(need - 1)
    _, whole = whole_image
    assert whole["served_logit_gap"] > 0.001 < whole["control_logit_gap"]
    # both cells of BENCHMARK.json take the whole-image route on the v5e,
    # with room to spare; a 10 GB bfloat16 checkpoint (a 20 GB image) cannot
    from benchmarks.harness.peaks import PEAKS
    hbm = PEAKS["TPU v5 lite"]["hbm_bytes"]
    for name in ("gpt2-medium", "gpt2-large"):
        real = _tiny.load_json("benchmarks", "configs", f"{name}.json")
        assert fits(hbm, real, 1024) and fits(hbm / 2, real, 1024)
    assert weights.image_bytes(GPT2, real) == 4 * 774_030_080
    assert not fits(hbm, dict(real, n_layer=230), 1024)     # 4.9 G leaves


# just too little room: the control beside the reference, the rows waiting
# on the device between layers; far too little: the reference alone (its
# head over blocks of compared rows only), the rows waiting on the host
@pytest.mark.parametrize("room,control", [("just short", True),
                                          ("far short", False)])
def test_the_layer_at_a_time_check_agrees_with_the_whole_image(
        monkeypatch, whole_image, room, control):
    import gc
    import weakref

    cfg = _tiny.tiny_cfg("gpt2-large", **ROUTE_CFG)
    answers, whole = whole_image
    hbm_bytes = room_needed(cfg) - 1 if room == "just short" else 300_000
    drawn, most_alive, real = [], [0], weights.make_weights

    def counted(family, cfg, seed, *, only=None, **kw):
        gc.collect()
        alive = sum(r().nbytes for r in drawn if r() is not None)
        out = real(family, cfg, seed, only=only, **kw)
        assert only is not None, "the whole image was made"
        drawn.extend(weakref.ref(v) for v in out.values())
        most_alive[0] = max(most_alive[0],
                            alive + sum(v.nbytes for v in out.values()))
        return out
    monkeypatch.setattr(weights, "make_weights", counted)
    one_layer = 4 * sum(int(np.prod(GPT2.model.leaves(cfg)[k][0][1:]))
                        for k in GPT2.model.BLOCK_KINDS)
    ends = 4 * sum(int(np.prod(GPT2.model.leaves(cfg)[k][0]))
                   for k in GPT2.model.TOP_KINDS)
    streamed = route_gaps(answers, hbm_bytes, control)
    assert len(drawn) == 2 + 12 * cfg["n_layer"] + 3
    # never more than one piece's float32 leaves at a time
    assert most_alive[0] <= max(one_layer, ends)
    assert streamed == {k: pytest.approx(whole[k], abs=2e-6)
                        for k in streamed}
    assert ("control_logit_gap" in streamed) == control
