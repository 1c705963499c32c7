#!/usr/bin/env python
"""Does the system still start on the chip? One process, the two main paths.

`python chip_smoke.py` (no arguments) is what the driver runs on a one-chip
machine. It refuses anything but a TPU, then:

  train   GPT-350M width (24 layers, h=1024, 16 heads of 64, vocab 50,304,
          rope / RMSNorm / SwiGLU), AMP O2 bf16, AdamW with f32 masters,
          `jit.train.TrainStep`: >= 5 steps at B=8 S=1024 on one fixed batch,
          then one compiled step at B=1 S=8192 (the chunked flash backward).
          Gates: finite losses, last < first, and the Mosaic custom call for
          the flash forward and BOTH backward kernels in the compiled HLO.
  kernel  the paged decode kernel against `decode_attention_xla` on the same
          pool at the three row counts the step programs use (S=1, 3, 64).
  serve   the same model behind `InferenceServer` + the continuous scheduler
          (`decode_kernel="pallas"`, speculation on, AOT warmup): /readyz,
          then HTTP /generate requests of mixed prompt and output lengths,
          some concurrent, one streamed. Gates: every answer 200 with the
          requested token count, full warmup coverage, zero post-ready
          compiles, the Mosaic call in all three step programs.

`python chip_smoke.py --four-chips` is the builder's run on a four-chip host
(the flash kernels under a dp=2 x mp=2 mesh against no mesh; dp=2 x mp=2
training against the one-chip loss; tp=2 serving through a replica fleet
under the same gates as above; bytes in use on each device).

Every failure raises: there is no phase whose error becomes a field of a
passing result. The last line of stdout is exactly one JSON object,
`{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`, with
the device as JAX reports it; the line before it is the full summary (what
each phase measured, compile seconds apart from run seconds, `"claim": null`). The phases are plain functions of their sizes, so tier-1 calls them
at a tiny width on the CPU (tests/test_chip_smoke.py); the command line always
keeps the refusal.
"""
import gc
import io
import json
import os
import sys
import threading
import time
import urllib.error
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np

MOSAIC_CALL = 'custom_call_target="tpu_custom_call"'
# the flash kernels a train step needs, by role; which backward variant a
# sequence length gets is the kernel module's own choice
FLASH_KERNELS = {"forward": ("_fwd_kernel",),
                 "dq": ("_dq_kernel", "_dq_kernel_chunked"),
                 "dkv": ("_dkv_kernel", "_dkv_kernel_chunked")}
STEP_PROGRAMS = ("prefill_chunk", "decode_step", "verify_step")
# |pallas - xla| on bf16 attention outputs of magnitude <= ~1: one bf16 ulp
# at 1.0 is 2^-7; the two paths round their probabilities independently
PAGED_ATOL = 2e-2
# the continuous scheduler's geometry (its constructor arguments) and the
# traffic sent to it: (prompt lengths, output lengths, how many at once)
SERVE_GEOMETRY = dict(max_slots=8, prefill_chunk=64, decode_steps=8,
                      block_size=32, num_blocks=192, spec_k=2,
                      max_new_tokens=48, max_seq_len=384)
SERVE_TRAFFIC = ((5, 200, 37, 330, 12, 96, 64, 3),
                 (16, 8, 40, 12, 48, 5, 24, 32), 5)


def log(msg):
    print(f"[chip_smoke] {msg}", flush=True)


# ------------------------------------------------------------------ device
class CacheEvents:
    """Hits and misses of the persistent compile cache, as JAX reports them
    through jax.monitoring."""

    def __init__(self):
        import jax

        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self)

    def __call__(self, event, **_kwargs):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def _cache_entries(cache_dir):
    return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0


def device_report(require_tpu=True):
    """Print what JAX runs on and the versions; refuse a non-TPU unless told
    otherwise (only the tests tell it otherwise)."""
    import jax
    import jaxlib

    dev = jax.devices()[0]
    try:
        import libtpu
        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = None
    report = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    log(f"device platform={dev.platform} kind={dev.device_kind!r} "
        f"count={report['count']} jax={jax.__version__} "
        f"jaxlib={jaxlib.__version__} libtpu={libtpu_version}")
    if require_tpu and dev.platform != "tpu":
        log(f"refusing to run: jax.devices()[0].platform is "
            f"{dev.platform!r}, not 'tpu'")
        raise SystemExit(2)
    from paddle_tpu.jit.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    log(f"compile cache: {cache_dir} ({_cache_entries(cache_dir)} entries "
        f"at start)")
    return report, cache_dir


def _free_device_state():
    import jax

    gc.collect()
    jax.clear_caches()


# ------------------------------------------------------------------- train
def _compile_step(step, x, y, expect_mosaic, num_layers):
    """AOT-compile one TrainStep shape; gate the flash kernels in its HLO.
    Kernel NAMES are read off the lowered module (the compiled text keeps
    only the call target), the call COUNT off the compiled one."""
    t0 = time.perf_counter()
    lowered_text = step.lowered(x, labels=y).as_text()
    compiled = step.aot_prime(x, labels=y)
    compile_s = time.perf_counter() - t0
    named = {name: n for names in FLASH_KERNELS.values() for name in names
             if (n := lowered_text.count(f'kernel_name = "{name}"'))}
    mosaic_calls = compiled.as_text().count(MOSAIC_CALL)
    if expect_mosaic:
        for role, names in FLASH_KERNELS.items():
            if sum(named.get(name, 0) for name in names) < num_layers:
                raise AssertionError(
                    f"the flash {role} kernel was lowered fewer than "
                    f"{num_layers} times, once per layer — the attention "
                    f"dispatch left the Pallas path: {named}")
        if mosaic_calls < 3 * num_layers:
            raise AssertionError(
                f"compiled step holds {mosaic_calls} Mosaic calls; forward + "
                f"two backward kernels over {num_layers} layers need "
                f"{3 * num_layers}")
    return compile_s, named, mosaic_calls


def train_phase(cfg, batch, seq, steps, long_seq, *, expect_mosaic=True,
                mesh=None):
    """A trainer that takes a few steps. Returns losses and timings; raises
    on a non-finite loss, a loss that did not fall, or a missing kernel."""
    import jax

    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    from paddle_tpu.jit.train import TrainStep
    from paddle_tpu.models.gpt import GPTForCausalLM

    if mesh is not None:
        dist.set_mesh(mesh)
    try:
        paddle.seed(0)
        model = GPTForCausalLM(cfg)
        paddle.amp.decorate(model, level="O2", dtype="bfloat16")
        opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                     parameters=model.parameters(),
                                     multi_precision=True)
        step = TrainStep(model, lambda logits, loss: loss, opt)
        rng = np.random.RandomState(0)

        def fixed_batch(b, s):
            ids = rng.randint(0, cfg.vocab_size, (b, s)).astype(np.int64)
            pair = (paddle.to_tensor(ids),
                    paddle.to_tensor(np.roll(ids, -1, axis=1)))
            if mesh is None:
                return pair
            # data parallelism is a layout: the batch axis over "dp"
            batch_over_dp = [dist.Shard(0), dist.Replicate()]
            return tuple(dist.shard_tensor(t, mesh, batch_over_dp)
                         for t in pair)

        x, y = fixed_batch(batch, seq)
        compile_s, named, calls = _compile_step(
            step, x, y, expect_mosaic, cfg.num_layers)
        log(f"train B={batch} S={seq}: compiled in {compile_s:.1f}s, "
            f"kernels {named}, {calls} Mosaic calls in the compiled HLO")
        losses = []
        t0 = time.perf_counter()
        for _ in range(steps):
            losses.append(float(step(x, labels=y)))   # float(): host sync
        run_s = time.perf_counter() - t0
        log(f"train losses {[round(v, 4) for v in losses]} "
            f"({steps} steps in {run_s:.2f}s, synced every step)")
        if not all(np.isfinite(losses)):
            raise AssertionError(f"non-finite loss: {losses}")
        if not losses[-1] < losses[0]:
            raise AssertionError(f"loss did not fall on a fixed batch: {losses}")
        out = {"batch": batch, "seq": seq, "steps": steps, "losses": losses,
               "compile_s": round(compile_s, 2), "run_s": round(run_s, 3),
               "kernels_lowered": named, "mosaic_calls_compiled": calls}

        if long_seq:
            xl, yl = fixed_batch(1, long_seq)
            lc, lnamed, lcalls = _compile_step(
                step, xl, yl, expect_mosaic, cfg.num_layers)
            t0 = time.perf_counter()
            long_loss = float(step(xl, labels=yl))
            long_run = time.perf_counter() - t0
            log(f"train B=1 S={long_seq}: compiled in {lc:.1f}s, kernels "
                f"{lnamed}, {lcalls} Mosaic calls, loss {long_loss:.4f} "
                f"in {long_run:.2f}s")
            if not np.isfinite(long_loss):
                raise AssertionError(f"non-finite loss at S={long_seq}")
            out["long"] = {"seq": long_seq, "loss": long_loss,
                           "compile_s": round(lc, 2),
                           "run_s": round(long_run, 3),
                           "kernels_lowered": lnamed,
                           "mosaic_calls_compiled": lcalls}
        peak = jax.devices()[0].memory_stats() or {}
        out["peak_bytes_in_use"] = peak.get("peak_bytes_in_use")
        return out
    finally:
        if mesh is not None:
            dist.set_mesh(None)


# ------------------------------------------------------------------ kernel
def paged_kernel_parity(cfg, geometry, *, expect_mosaic=True):
    """The paged Pallas kernel against the XLA reference on the same pool, at
    the shapes the three step programs call it with: slot-wide batch, the
    model's heads, the pool's pages, and S = 1 (decode), spec_k + 1 (verify),
    prefill_chunk. bf16. Raises past PAGED_ATOL."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas import decode_attention as da

    batch, heads = geometry["max_slots"], cfg.num_heads
    kv_heads = cfg.num_kv_heads or heads
    head_dim = cfg.hidden_size // heads
    block_size, num_blocks = geometry["block_size"], geometry["num_blocks"]
    table_width = geometry["max_seq_len"] // block_size
    row_counts = (1, geometry["spec_k"] + 1, geometry["prefill_chunk"])
    rng = np.random.default_rng(0)
    pool = (num_blocks, block_size, kv_heads * head_dim)
    k_pages = jnp.asarray(rng.standard_normal(pool), jnp.bfloat16)
    v_pages = jnp.asarray(rng.standard_normal(pool), jnp.bfloat16)
    tables = jnp.asarray(
        rng.permutation(num_blocks)[:batch * table_width]
        .reshape(batch, table_width), jnp.int32)
    out = {}
    for rows in row_counts:
        q = jnp.asarray(rng.standard_normal((batch, rows, heads, head_dim)),
                        jnp.bfloat16)
        lengths = jnp.asarray(
            rng.integers(0, table_width * block_size - rows, size=batch),
            jnp.int32)
        args = (q, k_pages, v_pages, tables, lengths)
        pallas = jax.jit(
            lambda *a: da.paged_decode_attention(*a, kernel="pallas")
        ).lower(*args).compile()
        calls = pallas.as_text().count(MOSAIC_CALL)
        if expect_mosaic and calls < 1:
            raise AssertionError(f"paged kernel at S={rows} compiled without "
                                 f"a Mosaic call")
        got = np.asarray(pallas(*args), np.float32)
        ref = np.asarray(jax.jit(
            lambda *a: da.paged_decode_attention(*a, kernel="xla"))(*args),
            np.float32)
        err = float(np.max(np.abs(got - ref)))
        log(f"paged kernel S={rows}: max|pallas-xla|={err:.4g} "
            f"(atol {PAGED_ATOL}), {calls} Mosaic call(s)")
        if not (np.isfinite(got).all() and err <= PAGED_ATOL):
            raise AssertionError(
                f"paged kernel S={rows} off the XLA reference by {err}")
        out[f"s{rows}"] = {"max_abs_err": err, "mosaic_calls": calls}
    out["atol"] = PAGED_ATOL
    return out


# ------------------------------------------------------------------- serve
def _post_generate(port, ids, want, stream=False, timeout=600):
    """POST /generate; returns (status, generated ids). The body is the npz
    the server documents; the output budget rides X-Max-New-Tokens."""
    buf = io.BytesIO()
    np.savez(buf, ids=np.asarray(ids, np.int64))
    headers = {"X-Max-New-Tokens": str(want),
               "X-Timeout-Ms": str(int(timeout * 1000))}
    if stream:
        headers["X-Stream"] = "sse"
    req = urllib.request.Request(f"http://127.0.0.1:{port}/generate",
                                 data=buf.getvalue(), headers=headers)
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        body = resp.read()
        status = resp.status
    if not stream:
        full = np.load(io.BytesIO(body))["out0"]
        if not np.array_equal(full[:len(ids)], ids):
            raise AssertionError("response does not start with the prompt")
        return status, [int(t) for t in full[len(ids):]]
    toks, done = [], False
    for event in body.decode().strip().split("\n\n"):
        fields = dict(line.split(": ", 1) for line in event.split("\n"))
        data = json.loads(fields["data"])
        if fields["event"] == "tokens":
            toks.extend(data["tokens"])
        elif fields["event"] == "done":
            done = True
        else:
            raise AssertionError(f"stream error event: {data}")
    if not done:
        raise AssertionError("stream ended without a done event")
    return status, toks


def _wait_ready(port, timeout):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/readyz", timeout=5) as r:
                if r.status == 200:
                    return
        except urllib.error.HTTPError as e:
            if e.code != 503:
                raise
        time.sleep(0.2)
    raise TimeoutError(f"/readyz not 200 within {timeout}s")


def _step_program_calls(pred):
    """Mosaic calls in each cached step program's compiled HLO. Arguments
    are abstract, laid out by the repo's own STEP_ARG_LABELS table."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models.generation import STEP_ARG_LABELS

    S, W = pred.max_slots, pred.table_width
    kv = pred.kv_cache
    width = {"prefill_chunk": pred.prefill_chunk,
             "decode_step": pred.decode_steps,
             "verify_step": pred.spec_k + 1}
    sds = jax.ShapeDtypeStruct

    def abstract(tree):     # weights and pools laid over a mesh stay so
        return jax.tree.map(
            lambda a: sds(a.shape, a.dtype, sharding=a.sharding if isinstance(
                a.sharding, jax.sharding.NamedSharding) else None), tree)

    slot_i32 = sds((S,), jnp.int32)
    by_label = {
        "state": abstract(pred.model._decode_state(jnp.dtype(kv.dtype))),
        "tokens": sds((S,), jnp.int64),
        "offsets": slot_i32, "chunk_lens": slot_i32, "lengths": slot_i32,
        "draft_lens": slot_i32, "max_lens": slot_i32, "top_ks": slot_i32,
        "active": sds((S,), jnp.bool_),
        "tables": sds((S, W), jnp.int32),
        "temperatures": sds((S,), jnp.float32),
        "k_pages": abstract(tuple(kv.k_pages)),
        "v_pages": abstract(tuple(kv.v_pages)),
        "rng_key": jax.random.key(0),
    }
    calls = {}
    for kind in STEP_PROGRAMS:
        labels = dict(by_label, chunk=sds((S, width[kind]), jnp.int64))
        args = tuple(labels[name] for name in STEP_ARG_LABELS[kind])
        compiled = pred.model.compiled_step_program(kind, S, width[kind], args)
        if compiled is None:
            raise AssertionError(f"step program {kind} is not in the cache")
        calls[kind] = compiled.as_text().count(MOSAIC_CALL)
    return calls


def _check_served(pred, num_layers, expect_mosaic):
    """What must hold of a predictor that has answered its requests: the AOT
    warmup covered every program, nothing compiled after ready, and each
    step program holds the Mosaic call once per layer."""
    stats = pred.warm_stats()
    if pred.warm_errors() or stats is None or stats["missing"]:
        raise AssertionError(f"AOT warmup incomplete: stats={stats} "
                             f"errors={pred.warm_errors()}")
    recompiles = {
        prog: int(pred._recompile_counter.labels(pred._component, prog).value)
        for prog in STEP_PROGRAMS}
    if any(recompiles.values()):
        raise AssertionError(f"post-ready compiles: {recompiles}")
    calls = _step_program_calls(pred)
    log(f"serve {pred._component}: warmup {stats['seconds']:.1f}s "
        f"({stats['compiled']}/{stats['programs']} programs compiled), "
        f"Mosaic calls per step program {calls}, post-ready compiles "
        f"{recompiles}")
    if expect_mosaic and min(calls.values()) < num_layers:
        raise AssertionError(
            f"a step program took the gather-to-dense XLA branch: "
            f"{calls} Mosaic calls for {num_layers} layers")
    return {"warmup_s": round(stats["seconds"], 2),
            "programs_compiled": stats["compiled"],
            "post_ready_compiles": recompiles, "mosaic_calls": calls}


def _requests(vocab, prompt_lens, wants, seed=1):
    rng = np.random.RandomState(seed)
    return [(rng.randint(0, vocab, n).astype(np.int64), w)
            for n, w in zip(prompt_lens, wants)]


def _serve_requests(port, requests, concurrent):
    """The first `concurrent` requests at once, the rest one by one, the
    last one streamed. Returns generated tokens per request, in order."""
    answers = [None] * len(requests)

    def one(i, stream=False):
        ids, want = requests[i]
        status, toks = _post_generate(port, ids, want, stream=stream)
        if status != 200 or len(toks) != want:
            raise AssertionError(
                f"request {i} (prompt {len(ids)}, want {want}): status "
                f"{status}, {len(toks)} tokens")
        answers[i] = toks

    errors = []

    def guarded(i):
        try:
            one(i)
        except BaseException as e:      # re-raised on the main thread below
            errors.append(e)

    threads = [threading.Thread(target=guarded, args=(i,))
               for i in range(concurrent)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=900)
    if errors:
        raise errors[0]
    if any(t.is_alive() for t in threads):
        raise TimeoutError("concurrent requests did not finish")
    for i in range(concurrent, len(requests)):
        one(i, stream=(i == len(requests) - 1))
    return answers


def serve_phase(cfg, geometry, traffic, *, expect_mosaic=True,
                compare_xla=True, ready_timeout=900):
    """A server that answers a few requests, over HTTP, through the
    continuous scheduler on the paged Pallas kernel."""
    import paddle_tpu as paddle
    from paddle_tpu.inference.scheduler import (
        ContinuousGenerateBatchingPredictor,
    )
    from paddle_tpu.inference.serving import InferenceServer
    from paddle_tpu.models.gpt import GPTForCausalLM

    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    model.eval()
    prompt_lens, wants, concurrent = traffic
    requests = _requests(cfg.vocab_size, prompt_lens, wants)
    t0 = time.perf_counter()
    pred = ContinuousGenerateBatchingPredictor(
        model, warmup=True, decode_kernel="pallas", **geometry)
    server = InferenceServer(None, generator=pred, default_timeout=600.0)
    server.start()
    try:
        _wait_ready(server.port, ready_timeout)
        ready_s = time.perf_counter() - t0
        t1 = time.perf_counter()
        answers = _serve_requests(server.port, requests, concurrent)
        run_s = time.perf_counter() - t1
        log(f"serve: /readyz 200 after {ready_s:.1f}s; {len(requests)} "
            f"requests answered in {run_s:.2f}s (prompts {list(prompt_lens)}, "
            f"outputs {list(wants)}, {concurrent} concurrent, last one "
            f"streamed)")
        checked = _check_served(pred, cfg.num_layers, expect_mosaic)
    finally:
        server.stop()
    out = {"requests": len(requests), "prompt_lens": list(prompt_lens),
           "new_tokens": list(wants), "ready_s": round(ready_s, 2),
           "run_s": round(run_s, 3), **checked}
    if compare_xla:
        # printed, not gated: a random-init model has near-tied logits
        # speculation off: greedy output is the same with one program fewer
        ref = ContinuousGenerateBatchingPredictor(
            model, decode_kernel="xla", **dict(geometry, spec_k=0))
        try:
            same = total = 0
            for (ids, want), toks in zip(requests, answers):
                full = np.asarray(ref.infer(ids, timeout=600,
                                            max_new_tokens=want))
                total += want
                same += int(np.sum(full[len(ids):] == np.asarray(toks)))
        finally:
            ref.close()
        out["token_agreement_pallas_vs_xla"] = round(same / total, 4)
        log(f"serve: token agreement pallas vs xla {same}/{total} "
            f"(printed, not gated)")
    return out


# -------------------------------------------------------------- four chips
def mesh_kernel_parity(batch, seq, heads, kv_heads, head_dim, dtype, atol, *,
                       expect_mosaic=True):
    """The flash kernels under a dp=2 x mp=2 mesh, the two ways they meet one.
    GSPMD cannot partition a Mosaic call, so on operands laid over the mesh
    `distributed.mesh.per_shard` runs the kernel per (batch, head) shard:
    checked with GQA, a flashmask index and the backward. Inside an enclosing
    shard_map (context parallelism) the kernel is called as it is. Both must
    equal the same call with no mesh."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    import paddle_tpu.distributed as dist
    from paddle_tpu.ops.pallas import flash_attention as pfa

    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.standard_normal((batch, seq, h, head_dim)),
                           dtype) for h in (heads, kv_heads, kv_heads))
    # flashmask: two documents, a row sees no key of the other one
    doc_end = np.where(np.arange(seq) < seq // 2, seq // 2, seq)
    index = jnp.asarray(np.tile(doc_end.reshape(1, 1, seq, 1),
                                (batch, 1, 1, 1)), jnp.int32)

    def flash(q, k, v):
        return pfa.flash_attention(q, k, v, causal=True)

    def loss(q, k, v):
        plain = flash(q, k, v)
        masked = pfa.flashmask_attention(q, k, v, index, causal=True)
        return jnp.sum((plain * jnp.cos(masked)).astype(jnp.float32)), plain

    def run(fn, *args):
        compiled = jax.jit(fn).lower(*args).compile()
        return compiled(*args), compiled.as_text().count(MOSAIC_CALL)

    with_grads = jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)
    want, _ = run(with_grads, q, k, v)      # ((loss, flash output), grads)
    mesh = dist.ProcessMesh(np.arange(4).reshape(2, 2), ["dp", "mp"])
    bshd = PartitionSpec("dp", None, "mp", None)
    dist.set_mesh(mesh)
    try:
        laid_out = [jax.device_put(a, NamedSharding(mesh.jax_mesh, bshd))
                    for a in (q, k, v)]
        got, calls = run(with_grads, *laid_out)
        got_inside, inner_calls = run(
            jax.shard_map(flash, mesh=mesh.jax_mesh, in_specs=bshd,
                          out_specs=bshd, check_vma=False), *laid_out)
    finally:
        dist.set_mesh(None)
    if expect_mosaic and min(calls, inner_calls) < 1:
        raise AssertionError(f"no Mosaic call under the mesh: {calls} under "
                             f"GSPMD, {inner_calls} inside a shard_map")
    pairs = zip(jax.tree.leaves((got, got_inside)),
                jax.tree.leaves((want, want[0][1])))
    err = max(float(np.max(np.abs(np.asarray(a, np.float32)
                                  - np.asarray(b, np.float32))
                           / np.maximum(1.0, np.abs(np.asarray(b, np.float32)))))
              for a, b in pairs)
    log(f"flash kernels under dp=2 x mp=2 (B={batch} S={seq} H={heads}/"
        f"{kv_heads} D={head_dim}): max err vs no mesh {err:.4g} (tol {atol}), "
        f"{calls} Mosaic calls under GSPMD, {inner_calls} inside a shard_map")
    if not err <= atol:
        raise AssertionError(f"flash kernels under a mesh off by {err}")
    return {"max_err": err, "tol": atol, "mosaic_calls": calls,
            "mosaic_calls_inside_shard_map": inner_calls}


def four_chip_phase(cfg, batch, seq, steps, geometry, traffic, *,
                    expect_mosaic=True):
    """One process, four chips: dp=2 x mp=2 training against the one-chip
    loss, tp=2 serving behind a two-replica fleet, bytes in use per device."""
    import jax

    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    from paddle_tpu.distributed.mesh import serving_mesh
    from paddle_tpu.inference.serving import InferenceServer, ReplicaFleet
    from paddle_tpu.models.gpt import GPTForCausalLM

    if len(jax.devices()) < 4:
        raise SystemExit(f"--four-chips needs 4 devices, JAX reports "
                         f"{len(jax.devices())}")
    one = train_phase(cfg, batch, seq, steps, None,
                      expect_mosaic=expect_mosaic)
    _free_device_state()
    mesh = dist.ProcessMesh(np.arange(4).reshape(2, 2), ["dp", "mp"])
    four = train_phase(cfg, batch, seq, steps, None,
                       expect_mosaic=expect_mosaic, mesh=mesh)
    _free_device_state()
    delta = abs(four["losses"][0] - one["losses"][0])
    log(f"four-chip train dp=2 x mp=2: first loss {four['losses'][0]:.4f} vs "
        f"one chip {one['losses'][0]:.4f} (|d|={delta:.4g})")
    # both runs hold bf16 weights and activations: the same sum in another
    # order moves the loss by a few bf16 ulps of the per-token terms
    if delta > 5e-2:
        raise AssertionError(f"dp2 x mp2 first-step loss off by {delta}")

    prompt_lens, wants, concurrent = traffic
    requests = _requests(cfg.vocab_size, prompt_lens, wants)
    serving_mesh(dp=2, tp=2)
    try:
        paddle.seed(0)
        model = GPTForCausalLM(cfg)
        model.eval()
        fleet = ReplicaFleet.build(model, n_replicas=2, warmup=True,
                                   decode_kernel="pallas", **geometry)
        server = InferenceServer(None, generator=fleet, default_timeout=600.0)
        server.start()
        try:
            t0 = time.perf_counter()
            _wait_ready(server.port, 900)       # "any replica ready"
            replicas = [r.predictor for r in fleet._snapshot()]
            while not all(p.ready() for p in replicas):
                if time.perf_counter() - t0 > 900:
                    raise TimeoutError("a fleet replica never became ready")
                time.sleep(0.2)
            ready_s = time.perf_counter() - t0
            _serve_requests(server.port, requests, concurrent)
            checked = [_check_served(p, cfg.num_layers, expect_mosaic)
                       for p in replicas]
            kv = replicas[0].kv_cache
            per_device = {
                str(d.id): (d.memory_stats() or {}).get("bytes_in_use")
                for d in jax.devices()}
            log(f"four-chip serve tp=2 x 2 replicas: {len(requests)} requests "
                f"answered, ready after {ready_s:.1f}s; kv tp_sharded="
                f"{kv.tp_sharded}; bytes_in_use per device {per_device}")
        finally:
            server.stop()
    finally:
        dist.set_mesh(None)
    return {"train_one_chip": one, "train_dp2_mp2": four,
            "first_loss_delta": delta,
            "serve": {"requests": len(requests), "ready_s": round(ready_s, 2),
                      "kv_tp_sharded": bool(kv.tp_sharded),
                      "replicas": checked,
                      "bytes_in_use_per_device": per_device}}


# -------------------------------------------------------------------- main
def result_lines(device, summary):
    """The two lines a passing run ends with: the summary, then the verdict
    the driver parses, which holds "ok" and "device" and nothing else."""
    return [f"[chip_smoke] summary {json.dumps(summary)}",
            json.dumps({"ok": True, "device": {
                "platform": str(device["platform"]),
                "kind": str(device["kind"]), "count": int(device["count"])}})]


def main(argv):
    four_chips = "--four-chips" in argv
    unknown = [a for a in argv if a != "--four-chips"]
    if unknown:
        raise SystemExit(f"usage: chip_smoke.py [--four-chips] "
                         f"(unknown: {unknown})")
    t_start = time.perf_counter()
    device, cache_dir = device_report(require_tpu=True)
    cache_events = CacheEvents()
    from paddle_tpu.models.gpt import gpt_350m

    cfg = gpt_350m(max_position=8192)
    result = {}
    if four_chips:
        result["flash_under_mesh"] = mesh_kernel_parity(
            2, 1024, 4, 2, cfg.hidden_size // cfg.num_heads, "bfloat16", 2e-2)
        result["four_chips"] = four_chip_phase(
            cfg, 8, 1024, 3, SERVE_GEOMETRY, SERVE_TRAFFIC)
    else:
        result["train"] = train_phase(cfg, 8, 1024, 5, 8192)
        _free_device_state()
        result["paged_kernel"] = paged_kernel_parity(cfg, SERVE_GEOMETRY)
        result["serve"] = serve_phase(cfg, SERVE_GEOMETRY, SERVE_TRAFFIC)
    result["compile_cache"] = {
        "dir": cache_dir, "hits": cache_events.hits,
        "misses": cache_events.misses, "entries": _cache_entries(cache_dir)}
    log(f"compile cache: {cache_events.hits} hits, {cache_events.misses} "
        f"misses, {result['compile_cache']['entries']} entries in {cache_dir}")
    result["wall_s"] = round(time.perf_counter() - t_start, 1)
    result["claim"] = None
    for line in result_lines(device, result):
        print(line, flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
