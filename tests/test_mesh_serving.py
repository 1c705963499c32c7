"""Mesh serving (ISSUE-12): tensor-parallel step programs + the replica fleet.

Tentpole acceptance, on the 8 fake CPU devices conftest forces for every
tier-1 run:

  * tp=2 sharded decode is TOKEN-IDENTICAL to the tp=1 run — greedy AND
    seeded-sampled — while the paged KV pool head-shards over tp so each
    chip resident-holds exactly 1/tp of the pool bytes.
  * ReplicaFleet routes least-loaded over ready replicas, honors drain
    (routing-only: the drained replica finishes its in-flight work),
    fails over around a killed replica with exactly-once terminals, and
    never recompiles across replica admit/retire/kill (all replicas run
    ONE shared model's cached step programs).
  * The fleet is a drop-in `generator` for InferenceServer: /readyz goes
    503 once no replica is ready, and the JSON /metrics snapshot carries
    per-replica states.
"""
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed.mesh import get_mesh, serving_mesh, set_mesh
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM


def _small_gpt():
    with paddle.utils.unique_name.guard():
        paddle.seed(7)
        m = GPTForCausalLM(GPTConfig(
            vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
            num_kv_heads=2, max_position=64, dropout=0.0))
    m.eval()
    return m


def _paged_tokens(m, prompts, NEW, **gen_kw):
    from paddle_tpu.inference.kv_cache import PagedKVCache

    cache = PagedKVCache(m.config.num_layers, m.config.num_kv_heads or 2,
                         m.config.hidden_size // m.config.num_heads,
                         block_size=8, num_blocks=24, dtype="float32")
    plens = np.asarray([len(p) for p in prompts])
    P = int(plens.max())
    batch = np.zeros((len(prompts), P), np.int64)
    for i, p in enumerate(prompts):
        batch[i, :len(p)] = p
    nb = max(cache.blocks_for(int(p) + NEW) for p in plens)
    for i in range(len(prompts)):
        cache.reserve(i, int(plens[i]) + NEW)
    tbl = np.stack([cache.block_table(i, pad_to=nb)
                    for i in range(len(prompts))])
    toks = np.asarray(m.generate_paged(batch, plens, cache, tbl,
                                       max_new_tokens=NEW,
                                       decode_kernel="xla", **gen_kw)._value)
    return toks, cache


def test_tp_sharded_decode_token_identity_and_kv_residency():
    """The tentpole parity gate: the SAME prompts decoded by the tp=2
    sharded step programs produce byte-identical tokens to the unsharded
    run — greedy and seeded-sampled — and the tp-sharded pool's per-chip
    bytes are exactly half the logical pool."""
    rng = np.random.default_rng(0)
    NEW = 5
    prompts = [rng.integers(0, 128, n).astype("int64") for n in (5, 9, 3)]
    sampled_kw = dict(temperature=0.8, top_k=40, seed=123)

    m = _small_gpt()
    ref_greedy, cache0 = _paged_tokens(m, prompts, NEW)
    ref_sampled, _ = _paged_tokens(m, prompts, NEW, **sampled_kw)
    assert not cache0.tp_sharded
    assert cache0.per_chip_pool_bytes() == cache0.pool_bytes()

    prev = get_mesh()
    serving_mesh(dp=1, tp=2)
    try:
        m2 = _small_gpt()  # same seed under the mesh -> tp-laid-out weights
        got_greedy, cache = _paged_tokens(m2, prompts, NEW)
        got_sampled, _ = _paged_tokens(m2, prompts, NEW, **sampled_kw)
    finally:
        set_mesh(prev)
    assert cache.tp_sharded
    np.testing.assert_array_equal(got_greedy, ref_greedy)
    np.testing.assert_array_equal(got_sampled, ref_sampled)
    # sampled path actually sampled something non-greedy on these shapes
    assert not np.array_equal(ref_sampled, ref_greedy)
    assert cache.pool_bytes() == cache0.pool_bytes()
    assert cache.per_chip_pool_bytes() * 2 == cache.pool_bytes()


# --------------------------------------------------------------- the fleet

_FLEET_KW = dict(max_slots=2, prefill_chunk=4, decode_steps=2,
                 max_new_tokens=3, decode_kernel="xla", block_size=8,
                 num_blocks=16, max_seq_len=16)

_PROMPT = np.array([5, 9, 2, 11], np.int64)


def test_a_trace_of_the_shared_model_hides_its_tracers_from_other_replicas():
    """Fleet replicas share one model, and a trace binds tracers into its
    parameters for its duration (Layer.functional_call). Another replica that
    reads the decode state meanwhile must wait for the real arrays, not carry
    the first one's tracers off: on the chip, where tracing 24 layers takes
    seconds, that killed the second replica's AOT warmup (PR 21)."""
    import jax

    m = _small_gpt()
    mid_trace, seen = threading.Event(), {}

    def pause(_layer, _inputs):     # runs inside the trace, parameters bound
        mid_trace.set()
        threading.Event().wait(0.5)

    hook = m.gpt.blocks[0].register_forward_pre_hook(pause)
    tracing = threading.Thread(target=lambda: seen.update(
        toks=_paged_tokens(m, [np.arange(5, dtype=np.int64)], 3)[0]))
    tracing.start()
    try:
        assert mid_trace.wait(60)
        state = m._decode_state(jax.numpy.bfloat16)
    finally:
        tracing.join(120)
        hook.remove()
    leaked = [k for k, v in state.items() if isinstance(v, jax.core.Tracer)]
    assert not leaked, leaked
    assert seen["toks"].shape == (1, 3)


def _reference(m):
    from paddle_tpu.inference.scheduler import (
        ContinuousGenerateBatchingPredictor,
    )

    pred = ContinuousGenerateBatchingPredictor(m, **_FLEET_KW)
    try:
        return pred.infer(_PROMPT, timeout=60)
    finally:
        pred.close()


def test_fleet_parity_drain_routing_and_dispatch_counters():
    from paddle_tpu.inference.serving import ReplicaFleet
    from paddle_tpu.observability.metrics import render_prometheus

    m = _small_gpt()
    ref = _reference(m)
    fleet = ReplicaFleet.build(m, n_replicas=2, **_FLEET_KW)
    try:
        for _ in range(3):
            np.testing.assert_array_equal(fleet.infer(_PROMPT, timeout=60),
                                          ref)
        toks = list(fleet.infer_stream(_PROMPT, timeout=60))
        np.testing.assert_array_equal(
            np.concatenate([_PROMPT] + [np.asarray(t) for t in toks]), ref)

        # drain r0: routing-only — every new dispatch lands on r1
        fleet.drain_replica("r0")
        assert fleet.replica_states() == {"r0": "draining", "r1": "ready"}
        np.testing.assert_array_equal(fleet.infer(_PROMPT, timeout=60), ref)
        fleet.undrain_replica("r0")
        assert fleet.replica_states()["r0"] == "ready"

        prom = render_prometheus(fleet.registry)
        assert 'paddle_fleet_replicas{state="ready"} 2' in prom
        # the drained dispatch could only have gone to r1
        r1_ok = [l for l in prom.splitlines()
                 if l.startswith("paddle_fleet_dispatch_total")
                 and 'replica="r1"' in l and 'outcome="ok"' in l]
        assert r1_ok and float(r1_ok[0].rsplit(" ", 1)[1]) >= 1
    finally:
        fleet.close()
    assert not fleet.ready()


def test_fleet_kill_failover_exactly_once_and_zero_recompiles():
    """ThreadDeath into one replica's batcher (restart budget 0 -> the
    permanent-503 death signal): the fleet marks it dead, re-dispatches to
    the sibling, terminals stay exactly-once (accepted == completed), and
    the shared program cache never grows across admit/kill/retire."""
    from paddle_tpu.inference.faults import FaultInjector, ThreadDeath
    from paddle_tpu.inference.serving import ReplicaFleet

    m = _small_gpt()
    ref = _reference(m)
    faults = FaultInjector()
    fleet = ReplicaFleet.build(
        m, n_replicas=2,
        replica_kwargs=[dict(faults=faults, max_restarts=0), {}],
        **_FLEET_KW)
    try:
        np.testing.assert_array_equal(fleet.infer(_PROMPT, timeout=60), ref)
        warm = len(m._generate_cache)

        third = fleet.add_replica()           # admit: shared cached programs
        np.testing.assert_array_equal(fleet.infer(_PROMPT, timeout=60), ref)

        faults.install("batcher.tick", error=ThreadDeath("test-kill"))
        sup = fleet._by_name("r0").predictor._sup
        deadline = 30.0
        import time
        t0 = time.monotonic()
        while sup.alive() and time.monotonic() - t0 < deadline:
            time.sleep(0.01)
        assert not sup.alive()

        # siblings absorb; the dead replica is observed and routed around
        for _ in range(3):
            np.testing.assert_array_equal(fleet.infer(_PROMPT, timeout=60),
                                          ref)
        assert fleet.replica_states()["r0"] == "dead"

        fleet.retire_replica(third)
        np.testing.assert_array_equal(fleet.infer(_PROMPT, timeout=60), ref)
        assert fleet.replica_states()[third] == "dead"

        assert len(m._generate_cache) == warm  # zero recompiles across churn

        snap = dict(fleet.metrics.snapshot())
        assert snap.get("accepted") == snap.get("completed")  # exactly-once
        assert snap.get("failed", 0) == 0 and snap.get("timeouts", 0) == 0
    finally:
        fleet.close()


def test_fleet_behind_inference_server_readyz_and_snapshot():
    from paddle_tpu.inference.serving import InferenceServer, ReplicaFleet

    m = _small_gpt()
    fleet = ReplicaFleet.build(m, n_replicas=2, **_FLEET_KW)
    srv = InferenceServer(None, batching=False, generator=fleet).start()
    base = f"http://127.0.0.1:{srv.port}"
    try:
        r = urllib.request.urlopen(base + "/readyz", timeout=30)
        assert r.status == 200

        import json
        snap = json.loads(
            urllib.request.urlopen(base + "/metrics", timeout=30).read())
        assert snap["replicas"] == {"r0": "ready", "r1": "ready"}

        # no ready replicas (all draining) -> 503 with Retry-After
        fleet.drain_replica("r0")
        fleet.drain_replica("r1")
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(base + "/readyz", timeout=30)
        assert ei.value.code == 503
        fleet.undrain_replica("r0")
        r = urllib.request.urlopen(base + "/readyz", timeout=30)
        assert r.status == 200
    finally:
        srv.stop()
