"""What a CPU can check of the chip bring-up (ISSUE 21): chip_smoke.py refuses
a machine without a TPU and its phases pass at a tiny width; the Pallas
kernels interpret on CPU only; an accelerator missing from the peak table is
an error; the compile cache is placed from outside or at one fixed path; a
crashed lint rule fails the lint's own gate."""
import os
import subprocess
import sys
import types

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402


# ------------------------------------------------------------ chip_smoke.py
def test_command_line_refuses_cpu_and_prints_no_result():
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=120)
    assert proc.returncode != 0
    assert "platform is 'cpu', not 'tpu'" in proc.stdout
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


def test_last_line_holds_the_verdict_and_nothing_else():
    import json

    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    summary, verdict = chip_smoke.result_lines(
        device, {"train": {"losses": [2.0, 1.0]}, "claim": None})
    assert not summary.startswith("{") and summary.endswith('"claim": null}')
    assert json.loads(verdict) == {"ok": True, "device": device}


TINY_GEOMETRY = dict(max_slots=2, prefill_chunk=8, decode_steps=2,
                     block_size=8, num_blocks=16, spec_k=2, max_new_tokens=6,
                     max_seq_len=32)
TINY_TRAFFIC = ((3, 20, 9, 14, 5, 11), (4, 2, 6, 3, 5, 4), 3)


def _tiny_config():
    from paddle_tpu.models.gpt import GPTConfig

    return GPTConfig(vocab_size=128, hidden_size=32, num_layers=1, num_heads=2,
                     max_position=64, use_rope=True, use_rms_norm=True,
                     use_swiglu=True)


def test_phases_pass_at_a_tiny_width():
    cfg = _tiny_config()
    train = chip_smoke.train_phase(cfg, 2, 32, 5, None, expect_mosaic=False)
    assert len(train["losses"]) == 5
    assert train["losses"][-1] < train["losses"][0]

    kernel = chip_smoke.paged_kernel_parity(cfg, TINY_GEOMETRY,
                                            expect_mosaic=False)
    assert set(kernel) == {"s1", "s3", "s8", "atol"}
    assert kernel["s3"]["max_abs_err"] <= chip_smoke.PAGED_ATOL

    serve = chip_smoke.serve_phase(cfg, TINY_GEOMETRY, TINY_TRAFFIC,
                                   expect_mosaic=False, compare_xla=False,
                                   ready_timeout=120)
    assert serve["requests"] == 6 and serve["programs_compiled"] == 3
    assert set(serve["post_ready_compiles"].values()) == {0}


def test_a_missing_kernel_fails_its_phase():
    """The Mosaic gate is live: on CPU no program holds a Mosaic call, so a
    phase run with the gate on must raise, not report a count of zero."""
    with pytest.raises(AssertionError, match="without a Mosaic call"):
        chip_smoke.paged_kernel_parity(_tiny_config(), TINY_GEOMETRY,
                                       expect_mosaic=True)


# ------------------------------------------------- no fallback hides the device
def test_interpret_mode_is_cpu_only(monkeypatch):
    from paddle_tpu.ops.pallas.flash_attention import _interpret

    assert _interpret() is True                         # this suite: cpu
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert _interpret() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="'gpu'"):
        _interpret()


def test_unknown_accelerator_kind_raises():
    from paddle_tpu.observability.xla import (
        device_ici_bandwidth,
        device_peak_flops,
    )

    v5e = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    assert device_peak_flops(v5e) == 197e12
    assert device_peak_flops(jax.devices()[0]) is None   # cpu: MFU absent
    unknown = types.SimpleNamespace(platform="tpu", device_kind="TPU v9")
    for table in (device_peak_flops, device_ici_bandwidth):
        with pytest.raises(ValueError, match="TPU v9"):
            table(unknown)


def test_asking_for_a_tpu_that_is_not_there_raises():
    import paddle_tpu as paddle

    with pytest.raises(RuntimeError, match="no accelerator"):
        paddle.set_device("tpu")
    with pytest.raises(RuntimeError, match="no accelerator"):
        paddle.TPUPlace(0)
    assert paddle.set_device("cpu").is_cpu_place()


def test_launcher_refuses_many_workers_on_one_tpu_host():
    from paddle_tpu.distributed.launch.context import Context, parse_args

    with pytest.raises(ValueError, match="one worker process per host"):
        Context(parse_args(["--backend", "tpu", "--nproc_per_node", "2",
                            "train.py"]))
    assert Context(parse_args(["--backend", "cpu", "--nproc_per_node", "2",
                               "train.py"])).world_size == 2


# ------------------------------------------------------------ compile cache
@pytest.fixture
def cache_config():
    """Run against the real jax config, then put it back: later tests must
    not start writing a persistent cache."""
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    saved = {n: getattr(jax.config, n) for n in names}
    yield
    for n, v in saved.items():
        jax.config.update(n, v)


def test_cache_dir_from_the_environment_is_never_overridden(
        monkeypatch, cache_config, tmp_path):
    from paddle_tpu.jit.compile_cache import enable_compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "ops"))
    assert enable_compile_cache(str(tmp_path / "mine")) == str(tmp_path / "ops")
    assert jax.config.jax_compilation_cache_dir == before   # set by no code
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0


def test_cache_dir_unset_is_one_fixed_path_across_processes(monkeypatch,
                                                            cache_config):
    from paddle_tpu.jit import compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert compile_cache.enable_compile_cache() == os.path.join(
        ROOT, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == os.path.join(
        ROOT, ".jax_cache")
    # two fresh processes in different directories agree on that path
    # without being told (the module is loaded by path and asked for the
    # directory alone: importing the package and jax is not under test)
    code = ("import importlib.util, sys; s = importlib.util."
            "spec_from_file_location('cc', sys.argv[1]); m = importlib.util."
            "module_from_spec(s); s.loader.exec_module(m); "
            "print(m.compile_cache_dir())")
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    procs = [subprocess.Popen(
        [sys.executable, "-c", code, compile_cache.__file__], env=env,
        cwd=cwd, stdout=subprocess.PIPE, text=True) for cwd in (ROOT, "/")]
    paths = [p.communicate(timeout=120)[0].strip() for p in procs]
    assert paths == [os.path.join(ROOT, ".jax_cache")] * 2


# --------------------------------------------------------------- lint gate
def test_a_crashed_rule_fails_the_self_check(monkeypatch, capsys):
    from paddle_tpu.analysis import rules
    from paddle_tpu.analysis.__main__ import main

    def broken(prog):
        raise KeyError("boom")

    monkeypatch.setitem(rules.RULES, "host-sync", broken)
    assert main(["--self-check", "--programs", "gpt_train"]) == 1
    out = capsys.readouterr().out
    assert "rule-error" in out and "host-sync crashed" in out
