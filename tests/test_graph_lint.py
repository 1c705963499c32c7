"""Graph lint (ISSUE-5): the paddle_tpu.analysis rule suite.

Two halves, both required by the acceptance bar:

1. every shipped rule is proven LIVE by a seeded-violation fixture program
   the analyzer must flag, and
2. the repo's own flagship programs (GPT/ResNet train steps, dense+paged
   decode) lint CLEAN at high severity — with the one intentional exception
   (CPU donation skip for the paged KV pools) carried by the builtin
   allowlist, visibly, with its justification.

Plus the integration surfaces: analyze_lowered (StableHLO-text subset),
and the CLI --self-check entry point.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.analysis as A

f32, bf16 = jnp.float32, jnp.bfloat16


def rules_of(report):
    return sorted({f.rule for f in report.findings})


# ------------------------------------------------- seeded violations (live)
def test_rule_donation_miss_fires():
    @jax.jit
    def step(state, x):
        return ({k: (v + x.sum()).astype(v.dtype) for k, v in state.items()},
                x.mean())

    state = {"w": jnp.zeros((512, 1024), f32)}            # 2 MiB, aliasable
    r = A.analyze(step, state, jnp.ones((8,), f32), _name="fix.donation")
    assert rules_of(r) == ["donation-miss"]
    (f,) = r.findings
    assert f.severity == A.HIGH and "w" in f.message and "2.0 MiB" in f.message
    # donate it -> clean
    fixed = jax.jit(step.__wrapped__, donate_argnums=(0,))
    r2 = A.analyze(fixed, state, jnp.ones((8,), f32), _name="fix.donated")
    assert [f for f in r2.findings if f.rule == "donation-miss"] == []


def test_rule_dtype_upcast_fires_on_bf16_matmul_upcast():
    @jax.jit
    def up(a, b):
        return jnp.dot(a.astype(f32), b.astype(f32))

    r = A.analyze(up, jnp.ones((4, 8), bf16), jnp.ones((8, 4), bf16),
                  _name="fix.upcast")
    assert rules_of(r) == ["dtype-upcast"]
    assert r.findings[0].severity == A.HIGH
    assert "bfloat16" in r.findings[0].message

    # the upcast survives layout ops on the way into the matmul
    @jax.jit
    def up2(a, b):
        return jnp.dot(a.astype(f32).T.reshape(8, 4).T, b)

    r2 = A.analyze(up2, jnp.ones((4, 8), bf16), jnp.ones((8, 4), f32),
                   _name="fix.upcast.layout")
    assert "dtype-upcast" in rules_of(r2)

    # a bf16 matmul with no upcast is clean
    @jax.jit
    def ok(a, b):
        return jnp.dot(a, b)

    r3 = A.analyze(ok, jnp.ones((4, 8), bf16), jnp.ones((8, 4), bf16),
                   _name="fix.clean")
    assert r3.findings == []


def test_rule_dtype_upcast_flags_strong_f64():
    r = A.analyze(jax.jit(lambda x: x * 2.0),
                  jnp.ones((8, 8), jnp.float64), _name="fix.f64")
    assert rules_of(r) == ["dtype-upcast"]
    assert "float64" in r.findings[0].message


def test_rule_host_sync_fires_inside_scan():
    @jax.jit
    def hs(x):
        def body(c, _):
            jax.debug.print("c={c}", c=c)
            return c + 1, c
        return jax.lax.scan(body, x, None, length=3)

    r = A.analyze(hs, jnp.float32(1.0), _name="fix.hostsync")
    assert rules_of(r) == ["host-sync"]
    f = r.findings[0]
    assert f.severity == A.HIGH and "debug_print" in f.message
    # cold-path programs only warn when the callback is outside any loop
    @jax.jit
    def warm(x):
        return jax.pure_callback(
            lambda a: a, jax.ShapeDtypeStruct(x.shape, x.dtype), x) * 2

    r2 = A.analyze(warm, jnp.ones((4,), f32), _name="fix.coldsync",
                   _hot=False)
    assert r2.findings[0].severity == A.WARN


def test_rule_constant_bloat_fires():
    big = np.ones((512, 1024), np.float32)                 # 2 MiB

    @jax.jit
    def cb(x):
        return (x + jnp.asarray(big)).astype(x.dtype)

    r = A.analyze(cb, jnp.ones((512, 1024), f32), _name="fix.const",
                  _donate_argnums=())
    assert "constant-bloat" in rules_of(r)
    f = [f for f in r.findings if f.rule == "constant-bloat"][0]
    assert f.severity == A.HIGH and "2.0 MiB" in f.message


def test_rule_recompile_hazard_static_args_and_weak_scalars():
    class Cfg:   # default identity hash/eq
        pass

    g = jax.jit(lambda x, cfg: x * 2, static_argnums=(1,))
    r = A.analyze(g, jnp.ones((4,), f32), Cfg(), _name="fix.identity")
    assert rules_of(r) == ["recompile-hazard"]
    assert r.findings[0].severity == A.HIGH
    assert "identity" in r.findings[0].message

    # unhashable static arg: the program refuses to trace; the analyzer
    # still reports the hazard instead of raising
    g2 = jax.jit(lambda x, opts: x * 2, static_argnums=(1,))
    r2 = A.analyze(g2, jnp.ones((4,), f32), ("a", [1, 2]),
                   _name="fix.unhashable")
    kinds = {(f.rule, f.severity) for f in r2.findings}
    assert ("recompile-hazard", A.HIGH) in kinds

    # weak-typed Python scalar argument
    r3 = A.analyze(jax.jit(lambda x, s: x * s), jnp.ones((4,), f32), 3.0,
                   _name="fix.weak")
    assert [(f.rule, f.severity) for f in r3.findings] == [
        ("recompile-hazard", A.WARN)]

    # weak-typed scalar captured by closure
    s = jnp.asarray(3.0)                                   # weak-typed 0-d

    @jax.jit
    def wc(x):
        return x * s

    r4 = A.analyze(wc, jnp.ones((4,), f32), _name="fix.weakconst")
    assert any(f.rule == "recompile-hazard" and "closed over" in f.message
               for f in r4.findings)


def test_rule_collective_axis_fires_on_mesh_mismatch():
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:8]).reshape(8), ("mp",))
    sm = jax.jit(shard_map(lambda x: jax.lax.psum(x, "mp"), mesh=mesh,
                           in_specs=P("mp"), out_specs=P()))
    x = jnp.ones((8, 4), f32)
    r = A.analyze(sm, x, _name="fix.collective", _mesh_axes=("dp",))
    assert rules_of(r) == ["collective-axis"]
    assert all(f.severity == A.HIGH for f in r.findings)
    msgs = " ".join(f.message for f in r.findings)
    assert "mp" in msgs and "dp" in msgs
    # same program against the mesh it was written for: clean
    r2 = A.analyze(sm, x, _name="fix.collective.ok", _mesh_axes=("mp",))
    assert r2.findings == []


# ----------------------------------------------------------------- allowlist
def test_allowlist_requires_reason_and_records_suppressions():
    with pytest.raises(ValueError, match="reason"):
        A.AllowlistEntry("donation-miss", reason="")
    entry = A.AllowlistEntry("donation-miss", subject="prog.*",
                             contains="pool", reason="intentional: xyz")
    f = A.Finding("donation-miss", A.HIGH, "pool not donated",
                  subject="prog.decode")
    other = A.Finding("host-sync", A.HIGH, "cb", subject="prog.decode")
    kept, suppressed = A.Allowlist([entry]).apply([f, other], backend="cpu")
    assert kept == [other]
    assert suppressed == [(f, entry)]
    # backend-gated entry does not suppress on other backends
    gated = A.AllowlistEntry("donation-miss", subject="prog.*",
                             reason="cpu only", backends=("cpu",))
    kept, suppressed = A.Allowlist([gated]).apply([f], backend="tpu")
    assert kept == [f] and suppressed == []


# ------------------------------------------------------------ analyze_lowered
def test_analyze_lowered_donation_and_callback():
    def step(state, x):
        jax.debug.print("x={x}", x=x)
        return {k: (v + x.sum()).astype(v.dtype) for k, v in state.items()}

    state = {"w": jnp.zeros((512, 1024), f32)}
    lowered = jax.jit(step).lower(state, jnp.ones((8,), f32))
    r = A.analyze_lowered(lowered, name="lowered.miss")
    rules = rules_of(r)
    assert "donation-miss" in rules and "host-sync" in rules
    # donated variant is clean of donation-miss
    lowered2 = jax.jit(step, donate_argnums=(0,)).lower(
        state, jnp.ones((8,), f32))
    r2 = A.analyze_lowered(lowered2, name="lowered.ok")
    assert "donation-miss" not in rules_of(r2)


# ----------------------------------------------------- repo programs (clean)
# report name -> ZOO_PROGRAMS key for the entries THIS file consumes. The
# old fixture built the whole 16-program zoo eagerly (the single largest
# tier-1 line, 60-80s: every tp/lora/verify variant traced and linted) while
# the tests below read exactly these six — so build per-entry, on first
# access; `python -m paddle_tpu.analysis --self-check` lints the full zoo.
_ZOO_KEY = {
    "train_step:GPT": "gpt_train",
    "train_step:ResNet18": "resnet_train",
    "gpt.decode.dense": "gpt_decode_dense",
    "gpt.decode.paged": "gpt_decode_paged",
    "gpt.decode.paged_prefill_chunk": "gpt_prefill_chunk",
    "gpt.decode.paged_step": "gpt_decode_step",
}


@pytest.fixture(scope="module")
def zoo_reports():
    from paddle_tpu.analysis.zoo import zoo_report

    cache = {}

    class _LazyZoo:
        def __getitem__(self, name):
            if name not in cache:
                cache[name] = zoo_report(_ZOO_KEY[name])
            return cache[name]

    return _LazyZoo()


def test_gpt_train_step_lints_clean(zoo_reports):
    assert zoo_reports["train_step:GPT"].high() == []


def test_resnet_train_step_lints_clean(zoo_reports):
    assert zoo_reports["train_step:ResNet18"].high() == []


def test_dense_decode_lints_clean(zoo_reports):
    assert zoo_reports["gpt.decode.dense"].high() == []


def test_paged_decode_clean_with_visible_cpu_donation_allowlist(zoo_reports):
    """The paged pools are donated only off-CPU (generation.py backend
    gate): on CPU the donation-miss findings must be SUPPRESSED by the
    builtin allowlist — visible with their justification, not silenced."""
    r = zoo_reports["gpt.decode.paged"]
    assert r.high() == []
    assert jax.default_backend() == "cpu"
    sup = [(f, e) for f, e in r.suppressed if f.rule == "donation-miss"]
    assert len(sup) == 4                      # k+v pools x 2 layers
    assert all("pages" in f.message for f, _ in sup)
    assert all("CPU backend" in e.reason for _, e in sup)


@pytest.mark.parametrize("name", ["gpt.decode.paged_prefill_chunk",
                                  "gpt.decode.paged_step"])
def test_continuous_step_programs_lint_clean(zoo_reports, name):
    """ISSUE-6 satellite: the continuous scheduler's two fixed-width step
    programs (prefill_chunk / decode_step) are in the zoo and lint clean —
    no host sync inside the tick scan, no recompile hazard from the
    slot-masked design, and the same CPU-only donation suppression as the
    other paged program (pools donated off-CPU)."""
    r = zoo_reports[name]
    assert r.high() == []
    sup = [f for f, _ in r.suppressed if f.rule == "donation-miss"]
    assert len(sup) == 4                      # k+v pools x 2 layers
    kept_rules = {f.rule for f in r.findings}
    assert "host-sync" not in kept_rules
    assert "recompile-hazard" not in kept_rules


def test_train_step_donation_rule_would_catch_dropped_donation():
    """Prove the donation rule actually guards TrainStep: the same GPT step
    program analyzed with donation stripped (tightened threshold so the
    smoke-sized params qualify) must flag the state leaves — i.e. if
    donate_argnums=(0, 1) were ever dropped from jit/train.py, the zoo gate
    would fail."""
    import paddle_tpu as paddle
    from paddle_tpu.jit.train import TrainStep
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    paddle.seed(0)
    cfg = GPTConfig(vocab_size=512, hidden_size=64, num_layers=1,
                    num_heads=4, max_position=64)
    model = GPTForCausalLM(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())
    step = TrainStep(model, lambda logits, loss: loss, opt)
    ids = np.random.RandomState(0).randint(0, 512, (2, 8))
    x = paddle.to_tensor(ids.astype("int64"))
    y = paddle.to_tensor(np.roll(ids, -1, axis=1).astype("int64"))
    tight = A.Thresholds(donation_min_bytes=64 << 10)
    # as shipped (donated): clean even at the tight threshold
    r = A.analyze_train_step(step, x, labels=y, thresholds=tight)
    assert all(f.rule != "donation-miss" for f in r.findings)
    # strip donation: the embedding (512x64 f32 = 128 KiB) must be flagged
    step._jitted = jax.jit(step._jitted.__wrapped__)       # no donate_argnums
    r2 = A.analyze_train_step(step, x, labels=y, thresholds=tight)
    assert any(f.rule == "donation-miss" and "state" in f.message
               for f in r2.findings)


# ------------------------------------------------------------------------ CLI
def test_cli_self_check_in_process(capsys):
    # a two-program subset keeps this leg inside the tier-1 per-test budget
    # (the full zoo is already linted by the module fixture above); paged
    # decode is in the subset so the allowlisted-suppression rendering runs
    from paddle_tpu.analysis.__main__ import main

    assert main(["--self-check", "--programs",
                 "gpt_train,gpt_decode_paged"]) == 0
    out = capsys.readouterr().out
    assert "CLEAN" in out and "allowlisted" in out


def test_cli_json_and_program_selection(capsys):
    from paddle_tpu.analysis.__main__ import main

    assert main(["--json", "--programs", "gpt_train"]) == 0
    payload = json.loads(capsys.readouterr().out)
    assert payload["status"] == "ok" and payload["high_total"] == 0
    assert [p["program"] for p in payload["programs"]] == ["train_step:GPT"]
    assert main(["--programs", "nope"]) == 2


def test_cli_list_rules_names_all_six(capsys):
    from paddle_tpu.analysis.__main__ import main

    assert main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rule in ("donation-miss", "dtype-upcast", "host-sync",
                 "constant-bloat", "recompile-hazard", "collective-axis"):
        assert rule in out


def test_report_render_and_dict_roundtrip():
    f = A.Finding("host-sync", A.WARN, "msg", where="file.py:1",
                  subject="p", remediation="fix it")
    r = A.Report("p", [f], [], ("host-sync",))
    assert "WARN" in r.render() and "fix it" in r.render()
    d = r.to_dict()
    assert d["by_rule"] == {"host-sync": 1} and d["high_total"] == 0


def test_donation_cross_check_against_memory_stats_alias_bytes():
    """Declared donation the backend silently ignored (alias_bytes == 0 in
    observability.xla.memory_stats) must surface as a warn — the HBM plan
    still holds both copies even though the code did the right thing."""

    class FakeMem:
        argument_size_in_bytes = 8 << 20
        output_size_in_bytes = 8 << 20
        temp_size_in_bytes = 0
        generated_code_size_in_bytes = 0
        alias_size_in_bytes = 0          # backend refused the aliasing

    class FakeCompiled:
        def memory_analysis(self):
            return FakeMem()

    @jax.jit
    def step(state, x):
        return ({k: (v + x.sum()).astype(v.dtype) for k, v in state.items()},
                x.mean())

    donated = jax.jit(step.__wrapped__, donate_argnums=(0,))
    state = {"w": jnp.zeros((512, 1024), f32)}
    r = A.analyze(donated, state, jnp.ones((8,), f32),
                  _name="fix.ignored_donation", _compiled=FakeCompiled())
    warns = [f for f in r.findings if f.rule == "donation-miss"]
    assert len(warns) == 1 and warns[0].severity == A.WARN
    assert "alias" in warns[0].message


def test_analyze_jaxpr_direct_with_donation_flags_and_names():
    """analyze_jaxpr is the no-retrace entry point: caller supplies the
    ClosedJaxpr plus per-invar donation flags and labels."""
    def step(state_w, x):
        return (state_w + x.sum()).astype(state_w.dtype), x.mean()

    closed = jax.make_jaxpr(step)(jnp.zeros((512, 1024), f32),
                                  jnp.ones((8,), f32))
    r = A.analyze_jaxpr(closed, donated=(False, False),
                        arg_names=("params.w", "batch"), name="raw.jaxpr")
    hits = [f for f in r.findings if f.rule == "donation-miss"]
    assert len(hits) == 1 and "params.w" in hits[0].message
    # same jaxpr, donation declared: clean
    r2 = A.analyze_jaxpr(closed, donated=(True, False), name="raw.ok")
    assert all(f.rule != "donation-miss" for f in r2.findings)
