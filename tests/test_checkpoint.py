"""Preemption-tolerant training (ISSUE-7): CheckpointManager async sharded
save/restore, bit-exact auto-resume through TrainStep and Model.fit,
fault-injected kill drills at the ckpt.* sites, torn/corrupt fallback,
retention, goodput accounting and crash-safe io_utils."""
import json
import os
import pickle
import tempfile

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.framework.checkpoint import (
    CheckpointCorruptWarning,
    CheckpointManager,
    latest_step,
)
from paddle_tpu.inference.faults import FaultInjector, ThreadDeath
from paddle_tpu.jit.train import TrainStep
from paddle_tpu.observability.training import StepMonitor


def _build(seed=0, lr=1e-2):
    paddle.seed(seed)
    model = nn.Sequential(nn.Linear(8, 16), nn.GELU(), nn.Linear(16, 4))
    opt = paddle.optimizer.AdamW(learning_rate=lr,
                                 parameters=model.parameters())
    loss_fn = nn.CrossEntropyLoss()
    return model, TrainStep(model, lambda o, y: loss_fn(o, y), opt)


def _batch(b=16, seed=0):
    rs = np.random.RandomState(seed)
    return (paddle.to_tensor(rs.randn(b, 8).astype("float32")),
            paddle.to_tensor(rs.randint(0, 4, b).astype("int64")))


def _params(step):
    return {k: np.asarray(t._value) for k, t in step._param_tensors.items()}


# ==================================================================== tentpole
def test_bit_exact_kill_resume_matches_uninterrupted():
    """The acceptance bar: train K steps -> kill -> auto-resume on a FRESH
    process stand-in (new model, different init) -> the K..2K losses and the
    final params are bit-identical to an uninterrupted 2K-step run."""
    K = 4
    x, y = _batch()
    _, full_step = _build(0)
    full_losses = [float(full_step(x, y)) for _ in range(2 * K)]
    full_params = _params(full_step)

    tmp = tempfile.mkdtemp()
    _, step_a = _build(0)
    mgr = CheckpointManager(tmp, async_save=True)
    pre = [float(step_a(x, y)) for _ in range(K)]
    assert pre == full_losses[:K]
    mgr.save(step_a, K)
    # mid-step kill: a couple more steps run but are never checkpointed —
    # the preempted process loses them, resume must retrace them exactly
    float(step_a(x, y))
    float(step_a(x, y))
    mgr.close()

    _, step_b = _build(123)            # deliberately different init
    mon = StepMonitor(peak_flops=None, lint=False)
    mon.bind(step_b)
    mgr2 = CheckpointManager(tmp)
    assert mgr2.restore(step_b) == K
    resumed = [float(step_b(x, y)) for _ in range(K)]
    assert resumed == full_losses[K:]
    got = _params(step_b)
    for k, want in full_params.items():
        np.testing.assert_array_equal(got[k], want, err_msg=k)
    assert mon.recompiles == 0         # restore must not change avals


def test_restore_is_bit_exact_for_run_steps_scan():
    """run_steps (the device-side multi-step scan) resumes bit-exactly too:
    counters/RNG restored so the precomputed per-step keys and LRs match."""
    x, y = _batch()
    _, full_step = _build(0)
    full = np.asarray(full_step.run_steps(6, x, y)._value)

    tmp = tempfile.mkdtemp()
    _, a = _build(0)
    first = np.asarray(a.run_steps(3, x, y)._value)
    np.testing.assert_array_equal(first, full[:3])
    CheckpointManager(tmp, async_save=False).save(a, 3)

    _, b = _build(9)
    assert CheckpointManager(tmp).restore(b) == 3
    rest = np.asarray(b.run_steps(3, x, y)._value)
    np.testing.assert_array_equal(rest, full[3:])


def test_mid_commit_kill_falls_back_to_previous_manifest():
    """ThreadDeath injected at ckpt.commit leaves a torn .tmp directory; the
    next restore must ignore it and land on the previous intact step."""
    x, y = _batch()
    tmp = tempfile.mkdtemp()
    inj = FaultInjector()
    _, step = _build(0)
    mgr = CheckpointManager(tmp, async_save=False, injector=inj)
    [float(step(x, y)) for _ in range(2)]
    mgr.save(step, 2)
    params_at_2 = _params(step)
    [float(step(x, y)) for _ in range(2)]
    inj.install("ckpt.commit", error=ThreadDeath())
    with pytest.raises(ThreadDeath):
        mgr.save(step, 4)
    # torn: data written, no manifest, no final dir
    assert os.path.isdir(os.path.join(tmp, "step_0000000004.tmp"))
    assert not os.path.isdir(os.path.join(tmp, "step_0000000004"))
    assert latest_step(tmp) == 2

    _, fresh = _build(7)
    mgr2 = CheckpointManager(tmp)
    assert mgr2.restore(fresh) == 2
    got = _params(fresh)
    for k, want in params_at_2.items():
        np.testing.assert_array_equal(got[k], want, err_msg=k)


def test_mid_snapshot_and_mid_serialize_kills_keep_previous_checkpoint():
    x, y = _batch()
    tmp = tempfile.mkdtemp()
    inj = FaultInjector()
    _, step = _build(0)
    mgr = CheckpointManager(tmp, async_save=False, injector=inj)
    float(step(x, y))
    mgr.save(step, 1)
    inj.install("ckpt.snapshot", error=ThreadDeath())
    with pytest.raises(ThreadDeath):
        mgr.save(step, 2)
    inj.install("ckpt.serialize", error=ThreadDeath())
    with pytest.raises(ThreadDeath):
        mgr.save(step, 3)
    assert CheckpointManager(tmp).steps() == [1]


def test_async_writer_failure_surfaces_on_next_save():
    x, y = _batch()
    tmp = tempfile.mkdtemp()
    inj = FaultInjector()
    _, step = _build(0)
    mgr = CheckpointManager(tmp, async_save=True, injector=inj)
    inj.install("ckpt.serialize", error=RuntimeError("disk on fire"))
    float(step(x, y))
    mgr.save(step, 1)
    with pytest.raises(RuntimeError, match="disk on fire"):
        mgr.wait()
    # the writer thread survives the failure and the next save lands
    float(step(x, y))
    mgr.save(step, 2)
    mgr.wait()
    assert mgr.latest_step() == 2
    mgr.close()


def test_corrupt_shard_falls_back_with_typed_warning():
    """A truncated/bit-flipped shard fails the manifest's size/crc check;
    restore warns (typed) and falls back to the previous intact manifest —
    never crashes, never loads garbage."""
    x, y = _batch()
    tmp = tempfile.mkdtemp()
    _, step = _build(0)
    mgr = CheckpointManager(tmp, async_save=False)
    float(step(x, y))
    mgr.save(step, 1)
    params_at_1 = _params(step)
    float(step(x, y))
    mgr.save(step, 2)

    data = os.path.join(tmp, "step_0000000002", "data_r0.npz")
    with open(data, "r+b") as f:       # truncate: the torn-write shape
        f.truncate(os.path.getsize(data) // 2)

    _, fresh = _build(5)
    mgr2 = CheckpointManager(tmp)
    with pytest.warns(CheckpointCorruptWarning, match="truncated"):
        assert mgr2.restore(fresh) == 1
    got = _params(fresh)
    for k, want in params_at_1.items():
        np.testing.assert_array_equal(got[k], want, err_msg=k)

    # bit-flip at same size: caught by crc32, same fallback
    mgr.save(step, 3)
    data3 = os.path.join(tmp, "step_0000000003", "data_r0.npz")
    raw = bytearray(open(data3, "rb").read())
    raw[len(raw) // 2] ^= 0xFF
    with open(data3, "wb") as f:
        f.write(bytes(raw))
    _, fresh2 = _build(6)
    with pytest.warns(CheckpointCorruptWarning, match="crc32"):
        assert CheckpointManager(tmp).restore(fresh2) == 1


def test_clock_skewed_saves_discovery_by_step_not_time():
    """Discovery orders by step number; a wildly skewed clock between saves
    (preempted VM, NTP jump) cannot make an older checkpoint look newest."""
    x, y = _batch()
    tmp = tempfile.mkdtemp()
    inj = FaultInjector()
    _, step = _build(0)
    mgr = CheckpointManager(tmp, async_save=False, injector=inj)
    float(step(x, y))
    inj.skew_clock(3600.0)             # save "an hour in the future"
    mgr.save(step, 1)
    assert mgr.last_timings["snapshot"] >= 0.0
    inj.skew_clock(7200.0)
    float(step(x, y))
    mgr.save(step, 2)
    for phase in ("snapshot", "serialize", "commit"):
        assert mgr.last_timings[phase] >= 0.0
    _, fresh = _build(3)
    assert CheckpointManager(tmp).restore(fresh) == 2


def test_retention_keep_last_plus_keep_every():
    x, y = _batch()
    tmp = tempfile.mkdtemp()
    _, step = _build(0)
    mgr = CheckpointManager(tmp, async_save=False, keep_last=2, keep_every=4)
    for i in range(1, 9):
        float(step(x, y))
        mgr.save(step, i)
    # keep-last-2 = {7, 8}; keep-every-4 = {4, 8}
    assert mgr.steps() == [4, 7, 8]
    # restore still works from a milestone
    _, fresh = _build(2)
    assert CheckpointManager(tmp).restore(fresh, step=4) == 4


def test_async_save_overlaps_and_second_save_queues():
    x, y = _batch()
    tmp = tempfile.mkdtemp()
    inj = FaultInjector()
    _, step = _build(0)
    mgr = CheckpointManager(tmp, async_save=True, injector=inj)
    inj.install("ckpt.serialize", delay=0.2)
    float(step(x, y))
    d = mgr.save(step, 1)              # returns before the write lands
    assert not os.path.isdir(d)
    float(step(x, y))
    mgr.save(step, 2)                  # queues behind the slow write
    mgr.wait()
    assert mgr.steps() == [1, 2]
    mgr.close()


def test_sharded_save_mesh_aware_restore(tmp_path):
    """Sharded params round-trip through the manager: replica-0 dedup on
    save, restore stitches chunks against the CURRENT (different) sharding
    — the process-count-changed resume path, on the 8-device CPU mesh."""
    import paddle_tpu.distributed as dist

    rng = np.random.default_rng(0)
    arrays = {"w1": rng.standard_normal((16, 8)).astype("float32"),
              "b": rng.standard_normal((24,)).astype("float32")}

    def provider_for(mesh_shape, placements):
        mesh = dist.ProcessMesh(
            np.arange(8).reshape(mesh_shape).tolist(), dim_names=["dp", "mp"])
        vals = {k: dist.shard_tensor(paddle.to_tensor(
            np.zeros_like(v) if placements is not arrangement_a else v),
            mesh, placements[k])._value for k, v in arrays.items()}

        class P:
            def export_state(self):
                return {"params": dict(vals), "acc": {},
                        "meta": {"step_count": 5, "seed": 5,
                                 "rng": [0, 0]}}

            def import_state(self, state):
                self.got = state

        return P()

    arrangement_a = {"w1": [dist.Shard(0), dist.Shard(1)],
                     "b": [dist.Replicate(), dist.Replicate()]}
    arrangement_b = {"w1": [dist.Shard(1), dist.Shard(0)],
                     "b": [dist.Shard(0), dist.Replicate()]}
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    mgr.save(provider_for((4, 2), arrangement_a), 5)
    target = provider_for((2, 4), arrangement_b)
    assert mgr.restore(target) == 5
    for k, want in arrays.items():
        got = np.asarray(target.got["params"][k])
        np.testing.assert_array_equal(got, want, err_msg=k)
    assert target.got["meta"]["step_count"] == 5


def test_empty_dir_restore_returns_none(tmp_path):
    _, step = _build(0)
    assert CheckpointManager(str(tmp_path)).restore(step) is None
    assert latest_step(str(tmp_path)) is None


# ===================================================== state export / import
def test_trainstep_export_import_no_recompile_counters_and_rng():
    """Satellite: export -> mutate -> import restores the step counter and
    RNG so the next launch reuses the cached executable — pinned via the
    PR 4 recompilation sentinel (zero recompiles across the whole dance)."""
    x, y = _batch()
    _, step = _build(0)
    mon = StepMonitor(peak_flops=None, lint=False)
    mon.bind(step)
    float(step(x, y))
    float(step(x, y))
    inner = getattr(step.optimizer, "_inner_opt", step.optimizer)
    snap = step.export_state()
    # host-materialize a stable copy (export returns live refs)
    snap_np = {
        "params": {k: np.asarray(v) for k, v in snap["params"].items()},
        "acc": {a: {k: np.asarray(v) for k, v in per.items()}
                for a, per in snap["acc"].items()},
        "meta": dict(snap["meta"]),
    }
    count_at_export, seed_at_export = inner._step_count, step._seed
    rng_at_export = paddle.get_rng_state()
    after_export = float(step(x, y))   # mutate past the export point
    float(step(x, y))
    assert inner._step_count == count_at_export + 2

    step.import_state(snap_np)
    assert inner._step_count == count_at_export
    assert step._seed == seed_at_export
    assert paddle.get_rng_state() == rng_at_export
    # the replayed step is bit-identical and does NOT recompile
    assert float(step(x, y)) == after_export
    assert mon.recompiles == 0

    # run_steps after import reuses its scan cache too: the FIRST scan is a
    # legitimately new program (counted), but re-importing and re-running
    # must add neither a fingerprint nor a recompile
    step.run_steps(2, x, y)
    n_avals = len(mon._seen_avals)
    recompiles_after_first_scan = mon.recompiles
    step.import_state(snap_np)
    step.run_steps(2, x, y)
    assert len(mon._seen_avals) == n_avals
    assert mon.recompiles == recompiles_after_first_scan


def test_export_state_meta_covers_lr_sched_and_monitor():
    x, y = _batch()
    paddle.seed(0)
    model = nn.Sequential(nn.Linear(8, 4))
    sched = paddle.optimizer.lr.StepDecay(learning_rate=0.1, step_size=2)
    opt = paddle.optimizer.Momentum(learning_rate=sched,
                                    parameters=model.parameters())
    loss_fn = nn.CrossEntropyLoss()
    step = TrainStep(model, lambda o, t: loss_fn(o, t), opt)
    mon = StepMonitor(peak_flops=None, lint=False)
    mon.bind(step)
    for _ in range(3):
        float(step(x, y))
        sched.step()
    snap = step.export_state()
    assert snap["meta"]["lr_sched"] == sched.state_dict()
    assert snap["meta"]["monitor"] == {"step_n": 3}

    _, other = _build(1)
    paddle.seed(0)
    model2 = nn.Sequential(nn.Linear(8, 4))
    sched2 = paddle.optimizer.lr.StepDecay(learning_rate=0.1, step_size=2)
    opt2 = paddle.optimizer.Momentum(learning_rate=sched2,
                                     parameters=model2.parameters())
    step2 = TrainStep(model2, lambda o, t: loss_fn(o, t), opt2)
    mon2 = StepMonitor(peak_flops=None, lint=False)
    mon2.bind(step2)
    step2.import_state(snap)
    assert sched2.state_dict() == sched.state_dict()
    assert mon2._step_n == 3           # metric series continues across resume

    # the fit ordering: restore FIRST, monitor binds later — the parked
    # counters must be adopted at bind so the series is still continuous
    paddle.seed(0)
    model3 = nn.Sequential(nn.Linear(8, 4))
    opt3 = paddle.optimizer.Momentum(
        learning_rate=paddle.optimizer.lr.StepDecay(learning_rate=0.1,
                                                    step_size=2),
        parameters=model3.parameters())
    step3 = TrainStep(model3, lambda o, t: loss_fn(o, t), opt3)
    step3.import_state(snap)
    mon3 = StepMonitor(peak_flops=None, lint=False)
    mon3.bind(step3)
    assert mon3._step_n == 3
    assert step3._pending_monitor_counters is None


# ===================================================================== goodput
def test_goodput_accounting_on_fake_clock():
    t = [0.0]

    def clock():
        return t[0]

    mon = StepMonitor(peak_flops=None, lint=False, clock=clock, loss_every=0)
    # restore before the first step: 2s of resume cost enter the wall window
    mon.checkpoint_phase("restore", 2.0)
    # 3 steps of 1s each with a 0.5s checkpoint snapshot between
    for _ in range(3):
        t0 = mon.step_begin()
        t[0] += 1.0
        mon.step_end(object(), None, t0)
    mon.checkpoint_phase("snapshot", 0.5)
    t[0] += 0.5
    # wall = 2 (restore) + 3 (steps) + 0.5 (snapshot) = 5.5; useful = 3
    assert mon.goodput == pytest.approx(3.0 / 5.5)
    assert mon.useful_step_seconds == pytest.approx(3.0)
    assert mon.checkpoint_seconds == pytest.approx(2.5)
    mon.checkpoint_result(ok=True, step=3)
    mon.checkpoint_result(ok=False)
    text = mon.render()
    assert "paddle_train_goodput" in text
    assert ('paddle_train_checkpoint_seconds_count{phase="snapshot"} 1'
            in text)
    assert ('paddle_train_checkpoint_seconds_count{phase="restore"} 1'
            in text)
    assert 'paddle_train_checkpoints_total{result="committed"} 1' in text
    assert 'paddle_train_checkpoints_total{result="failed"} 1' in text
    names = [s.name for s in mon.tracer.spans()]
    assert "ckpt_restore" in names and "ckpt_snapshot" in names


def test_manager_feeds_monitor_phases(tmp_path):
    x, y = _batch()
    _, step = _build(0)
    mon = StepMonitor(peak_flops=None, lint=False)
    mon.bind(step)
    mgr = CheckpointManager(str(tmp_path), async_save=False, monitor=mon)
    float(step(x, y))
    mgr.save(step, 1)
    text = mon.render()
    for phase in ("snapshot", "serialize", "commit"):
        assert (f'paddle_train_checkpoint_seconds_count{{phase="{phase}"}} 1'
                in text)
    assert 'paddle_train_checkpoints_total{result="committed"} 1' in text
    assert mon.goodput is not None and 0.0 < mon.goodput <= 1.0


# ================================================================ hapi Model.fit
class _LossRecorder:
    def __init__(self):
        self.losses = []

    # duck-typed Callback: CallbackList dispatches any on_* by name
    def set_model(self, model):
        self.model = model

    def __getattr__(self, name):
        if name.startswith("on_"):
            if name == "on_batch_end":
                return self._on_batch_end
            return lambda *a, **k: None
        raise AttributeError(name)

    def _on_batch_end(self, mode, step, logs=None):
        if mode == "train":
            self.losses.append(logs["loss"][0])


class _Killer(_LossRecorder):
    def __init__(self, after):
        super().__init__()
        self.after = after

    def _on_batch_end(self, mode, step, logs=None):
        super()._on_batch_end(mode, step, logs)
        if len(self.losses) >= self.after:
            raise ThreadDeath()


def _fit_model(seed):
    from paddle_tpu.hapi.model import Model

    paddle.seed(seed)
    m = Model(nn.Sequential(nn.Linear(8, 16), nn.GELU(), nn.Linear(16, 4)))
    loss_fn = nn.CrossEntropyLoss()
    m.prepare(
        optimizer=paddle.optimizer.AdamW(
            learning_rate=1e-2, parameters=m.network.parameters()),
        loss=lambda o, t: loss_fn(o, t))
    return m


def _fit_data():
    rs = np.random.RandomState(0)
    X = rs.randn(32, 8).astype("float32")
    Y = rs.randint(0, 4, (32, 1)).astype("int64")
    return [(X[i], Y[i]) for i in range(32)]


def test_fit_kill_auto_resume_bit_exact(tmp_path):
    """fit(checkpoint_dir=..., resume='auto'): killed mid-epoch-2 via an
    injected ThreadDeath, a FRESH model resumes from the last periodic
    checkpoint and reproduces the uninterrupted loss trajectory bit-exactly
    (epoch boundaries included)."""
    ds = _fit_data()
    base = _LossRecorder()
    _fit_model(0).fit(ds, batch_size=4, epochs=2, shuffle=False, verbose=0,
                      callbacks=[base])
    assert len(base.losses) == 16

    d = str(tmp_path / "ck")
    killer = _Killer(11)               # dies in epoch 1 (0-based), batch 3
    with pytest.raises(ThreadDeath):
        _fit_model(0).fit(ds, batch_size=4, epochs=2, shuffle=False,
                          verbose=0, callbacks=[killer],
                          checkpoint_dir=d, checkpoint_every=4)
    assert killer.losses == base.losses[:11]
    assert latest_step(d) == 8         # periodic saves at 4 and 8

    rec = _LossRecorder()
    _fit_model(99).fit(ds, batch_size=4, epochs=2, shuffle=False, verbose=0,
                       callbacks=[rec], checkpoint_dir=d, checkpoint_every=4)
    # resumed from global step 8 = epoch 1 batch 0; steps 9..16 must match
    assert rec.losses == base.losses[8:]
    # graceful completion flushed the final state synchronously
    assert latest_step(d) == 16


def test_fit_graceful_completion_flush_and_noop_resume(tmp_path):
    ds = _fit_data()
    d = str(tmp_path / "ck")
    rec = _LossRecorder()
    _fit_model(0).fit(ds, batch_size=4, epochs=1, shuffle=False, verbose=0,
                      callbacks=[rec], checkpoint_dir=d)
    assert latest_step(d) == 8         # final flush even without periodic
    again = _LossRecorder()
    _fit_model(1).fit(ds, batch_size=4, epochs=1, shuffle=False, verbose=0,
                      callbacks=[again], checkpoint_dir=d)
    assert again.losses == []          # fully trained: nothing re-runs
    # raising the horizon resumes from the flush, continuing the trajectory
    more = _LossRecorder()
    _fit_model(2).fit(ds, batch_size=4, epochs=2, shuffle=False, verbose=0,
                      callbacks=[more], checkpoint_dir=d)
    assert len(more.losses) == 8
    base = _LossRecorder()
    _fit_model(0).fit(ds, batch_size=4, epochs=2, shuffle=False, verbose=0,
                      callbacks=[base])
    assert more.losses == base.losses[8:]


def test_fit_resume_never_starts_fresh(tmp_path):
    ds = _fit_data()
    d = str(tmp_path / "ck")
    with pytest.raises(ThreadDeath):
        _fit_model(0).fit(ds, batch_size=4, epochs=1, shuffle=False,
                          verbose=0, callbacks=[_Killer(6)],
                          checkpoint_dir=d, checkpoint_every=4)
    rec = _LossRecorder()
    _fit_model(0).fit(ds, batch_size=4, epochs=1, shuffle=False, verbose=0,
                      callbacks=[rec], checkpoint_dir=d, checkpoint_every=4,
                      resume="never")
    assert len(rec.losses) == 8        # resume disabled: full epoch re-runs


# ============================================================ io_utils satellites
def test_save_is_crash_safe_torn_write_keeps_old_file(tmp_path, monkeypatch):
    """A preemption mid-pickle must never leave a truncated file where a
    good checkpoint was: the write goes to a temp file and only an fsynced
    complete file is renamed over the old one."""
    from paddle_tpu.framework import io_utils

    path = str(tmp_path / "state.pdparams")
    good = {"w": paddle.to_tensor(np.arange(4, dtype="float32"))}
    io_utils.save(good, path)
    good_bytes = open(path, "rb").read()

    real_dump = pickle.dump
    def torn_dump(obj, f, protocol=None):
        f.write(b"\x80\x04partial-garbage")   # some bytes land...
        raise ThreadDeath()                    # ...then the process dies

    monkeypatch.setattr(io_utils.pickle, "dump", torn_dump)
    with pytest.raises(ThreadDeath):
        io_utils.save({"w": paddle.to_tensor(np.zeros(4, "float32"))}, path)
    monkeypatch.setattr(io_utils.pickle, "dump", real_dump)

    assert open(path, "rb").read() == good_bytes   # old file untouched
    assert [n for n in os.listdir(tmp_path) if ".tmp" in n] == []
    loaded = paddle.load(path)
    np.testing.assert_array_equal(np.asarray(loaded["w"]._value),
                                  np.arange(4, dtype="float32"))


def test_save_load_roundtrip_params_opt_state_nested():
    """Satellite: the full training-state shape — params (Tensors), optimizer
    state (@step int + accumulator Tensors + LR dict), nested containers and
    plain ndarrays — round-trips with types preserved and no _TensorPayload
    leaking."""
    from paddle_tpu.framework.io_utils import _TensorPayload
    from paddle_tpu.tensor import Tensor

    paddle.seed(0)
    model = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 2))
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
    x = paddle.to_tensor(np.random.RandomState(0).randn(2, 4).astype("f"))
    loss = model(x).sum()
    loss.backward()
    opt.step()

    state = {
        "model": model.state_dict(),
        "opt": opt.state_dict(),
        "extra": {"history": [1.5, 2.5], "arrays": np.arange(6).reshape(2, 3),
                  "tup": (np.float32(1.0), "tag", None)},
    }
    with tempfile.TemporaryDirectory() as d:
        p = os.path.join(d, "ck.pdparams")
        paddle.save(state, p)
        loaded = paddle.load(p)

    def no_payloads(obj):
        if isinstance(obj, _TensorPayload):
            return False
        if isinstance(obj, dict):
            return all(no_payloads(v) for v in obj.values())
        if isinstance(obj, (list, tuple)):
            return all(no_payloads(v) for v in obj)
        return True

    assert no_payloads(loaded)
    for k, v in state["model"].items():
        assert isinstance(loaded["model"][k], Tensor), k
        np.testing.assert_array_equal(np.asarray(loaded["model"][k]._value),
                                      np.asarray(v._value))
    assert loaded["opt"]["@step"] == 1
    for k, v in state["opt"].items():
        if isinstance(v, Tensor):
            assert isinstance(loaded["opt"][k], Tensor), k
            np.testing.assert_array_equal(
                np.asarray(loaded["opt"][k]._value), np.asarray(v._value))
    np.testing.assert_array_equal(loaded["extra"]["arrays"],
                                  state["extra"]["arrays"])
    assert isinstance(loaded["extra"]["arrays"], np.ndarray)
    assert loaded["extra"]["tup"] == state["extra"]["tup"]
    assert loaded["extra"]["history"] == [1.5, 2.5]


def test_all_ndarray_dict_roundtrips_and_reference_converts(tmp_path):
    """The ambiguity fix: OUR save of an all-ndarray dict round-trips as
    ndarrays (the marker routes it through _unpack), while a marker-less
    all-ndarray pickle — a real reference DenseTensor state dict — now
    converts to Tensors instead of leaking raw arrays."""
    from paddle_tpu.tensor import Tensor

    ours = {"a": np.arange(4, dtype="float32"),
            "b": np.ones((2, 2), dtype="int64")}
    p = str(tmp_path / "ours.pdparams")
    paddle.save(ours, p)
    loaded = paddle.load(p)
    for k in ours:
        assert isinstance(loaded[k], np.ndarray), k
        np.testing.assert_array_equal(loaded[k], ours[k])

    # byte-shape of a real reference checkpoint whose values all reduced to
    # bare ndarrays (DenseTensor path) — previously ambiguous, now converted
    ref = str(tmp_path / "ref.pdparams")
    with open(ref, "wb") as f:
        pickle.dump(ours, f, protocol=4)
    ref_loaded = paddle.load(ref)
    for k in ours:
        assert isinstance(ref_loaded[k], Tensor), k
        np.testing.assert_array_equal(np.asarray(ref_loaded[k]._value),
                                      ours[k])


# ================================================================ slow soak
@pytest.mark.slow
def test_kill_resume_churn_soak():
    """Soak: a run preempted at EVERY save point (kill injected alternately
    mid-snapshot / mid-serialize / mid-commit, plus plain mid-step deaths),
    resumed each time by a freshly-built process stand-in — the final loss
    trajectory is still bit-identical to the uninterrupted run."""
    TOTAL, EVERY = 24, 3
    x, y = _batch()
    _, full_step = _build(0)
    full_losses = [float(full_step(x, y)) for _ in range(TOTAL)]

    tmp = tempfile.mkdtemp()
    sites = ["ckpt.commit", "ckpt.serialize", "ckpt.snapshot", None]
    done, losses, cycle = 0, [], 0
    while done < TOTAL:
        _, step = _build(cycle * 17)   # every incarnation inits differently
        inj = FaultInjector()
        mgr = CheckpointManager(tmp, async_save=False, injector=inj)
        restored = mgr.restore(step)
        done = restored or 0
        losses = losses[:done]
        site = sites[cycle % len(sites)]
        cycle += 1
        saves_this_cycle = 0
        try:
            while done < TOTAL:
                losses.append(float(step(x, y)))
                done += 1
                if done % EVERY == 0:
                    if (site is not None and done < TOTAL
                            and saves_this_cycle == 1):
                        # die on the SECOND save: one checkpoint committed
                        # per incarnation, so the run makes real progress
                        # through every kill site
                        inj.install(site, error=ThreadDeath())
                    mgr.save(step, done)
                    saves_this_cycle += 1
            mgr.save(step, TOTAL)
        except ThreadDeath:
            continue   # preempted: next incarnation resumes from disk
    assert losses == full_losses
    assert cycle >= 4  # the drill actually exercised every kill site


# ========================================================== manifest internals
def test_manifest_records_files_meta_and_is_json(tmp_path):
    x, y = _batch()
    _, step = _build(0)
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    float(step(x, y))
    mgr.save(step, 1)
    mpath = os.path.join(tmp_path, "step_0000000001", "manifest.json")
    manifest = json.load(open(mpath))
    assert manifest["step"] == 1
    assert manifest["meta"]["step_count"] == 1
    assert list(manifest["files"]) == ["data_r0.npz"]
    info = manifest["files"]["data_r0.npz"]
    data = os.path.join(tmp_path, "step_0000000001", "data_r0.npz")
    assert info["bytes"] == os.path.getsize(data)
    # every params/acc leaf has a chunked tensor entry
    assert any(k.startswith("params.") for k in manifest["keys"])
    assert any(k.startswith("acc.") for k in manifest["keys"])


# ======================================== SIGTERM preemption flush (ISSUE-8)
class _Preemptor(_LossRecorder):
    """Delivers SIGTERM to this very process after N batches — the launch
    controller's stop_pod seen from inside the worker."""

    def __init__(self, after):
        super().__init__()
        self.after = after

    def _on_batch_end(self, mode, step, logs=None):
        super()._on_batch_end(mode, step, logs)
        if mode == "train" and len(self.losses) == self.after:
            import signal as _signal

            os.kill(os.getpid(), _signal.SIGTERM)


def test_fit_sigterm_flushes_synchronously_and_exits_elastic(tmp_path):
    """ROADMAP item 5 satellite: SIGTERM during fit(checkpoint_dir=...)
    triggers a final SYNCHRONOUS CheckpointManager flush at the next batch
    boundary and raises PreemptionExit carrying ELASTIC_EXIT_CODE — the
    contract only the legacy AutoCheckpointer spoke before. The flushed
    step is the PREEMPTED one (5), not merely the last periodic save (4),
    and a fresh model resumes from it bit-exactly."""
    import signal as _signal

    from paddle_tpu.distributed.fleet.elastic import ELASTIC_EXIT_CODE
    from paddle_tpu.framework.checkpoint import PreemptionExit

    ds = _fit_data()
    base = _LossRecorder()
    _fit_model(0).fit(ds, batch_size=4, epochs=2, shuffle=False, verbose=0,
                      callbacks=[base])
    assert len(base.losses) == 16

    sentinel = lambda *a: None                  # noqa: E731
    prev = _signal.signal(_signal.SIGTERM, sentinel)
    try:
        d = str(tmp_path / "ck")
        pre = _Preemptor(5)
        with pytest.raises(PreemptionExit) as ei:
            _fit_model(0).fit(ds, batch_size=4, epochs=2, shuffle=False,
                              verbose=0, callbacks=[pre],
                              checkpoint_dir=d, checkpoint_every=4)
        assert ei.value.code == ELASTIC_EXIT_CODE == 101
        assert pre.losses == base.losses[:5]
        # the SIGTERM flush landed step 5 synchronously (periodic was 4)
        assert latest_step(d) == 5
        # fit restored the previous (sentinel) handler on the way out
        assert _signal.getsignal(_signal.SIGTERM) is sentinel

        rec = _LossRecorder()
        _fit_model(99).fit(ds, batch_size=4, epochs=2, shuffle=False,
                           verbose=0, callbacks=[rec], checkpoint_dir=d,
                           checkpoint_every=4)
        assert rec.losses == base.losses[5:]    # resumes AT the preemption
        assert latest_step(d) == 16             # graceful completion flush
    finally:
        _signal.signal(_signal.SIGTERM, prev)


def test_preemption_flush_outside_main_thread_degrades_gracefully():
    """PreemptionFlush.install() from a worker thread (signals undeliverable
    there) must not crash fit — it degrades to poll-only mode."""
    import threading as _threading

    from paddle_tpu.framework.checkpoint import PreemptionFlush

    got = {}

    def off_main():
        fl = PreemptionFlush().install()
        got["installed"] = fl.installed
        fl.restore()                            # no-op, must not raise

    t = _threading.Thread(target=off_main)
    t.start()
    t.join(10)
    assert got == {"installed": False}
