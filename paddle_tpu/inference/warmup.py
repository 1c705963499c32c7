"""AOT warmup + the post-ready compile sentinel (ISSUE-13 runtime half).

The compile-surface lint (analysis/compilesurface.py) proves a serving
configuration's program inventory is CLOSED; this module makes the runtime
honor it:

* ``AOTWarmup`` derives the continuous scheduler's ServingConfig, takes its
  manifest program keys, and launches each step program ONCE with fully
  idle inputs (all slots masked, zero chunk lengths) so every cache key
  lands in the shared ``GenerationMixin._generate_cache`` before the
  predictor reports ready. Idle launches are write-free: the valid masks
  drop every KV scatter and commit() re-installs byte-identical pools, so
  warmup is safe next to a live pool. With ``cache_dir`` set
  (jit/compile_cache.py; ``JAX_COMPILATION_CACHE_DIR`` overrides it), XLA's
  persistent compilation cache turns a process restart into a warm start
  (trace only — the docs/DEPLOYMENT.md cold-start runbook).

* The **post-ready compile sentinel** is the serving twin of the PR 4
  training sentinel (observability/training.py StepMonitor): once warmup
  has covered the manifest, any ``_runner_for`` cold build is a contract
  violation — the scheduler counts it in
  ``paddle_serving_recompiles_total{component,program}`` and notifies the
  active ``CompileSentinel``, which every chaos-marked test arms
  (tests/conftest.py) and fails on. Launch-argument shapes are
  fingerprinted with the SAME helper the training sentinel uses
  (jit/fingerprint.py), so the two sentinels cannot drift on what "the
  same program" means.
"""
from __future__ import annotations

import collections
import time

import numpy as np

from ..analysis.compilesurface import ServingConfig
from ..analysis.lockwitness import make_lock
from ..jit.compile_cache import enable_compile_cache
from ..jit.fingerprint import aval_fingerprint

__all__ = ["AOTWarmup", "CompileSentinel", "serving_config_of",
           "activate", "deactivate", "notify"]


# ------------------------------------------------------------ the sentinel
class CompileSentinel:
    """Records post-ready cold builds. Appends are deque-atomic, so the
    batcher thread writes and the test thread reads without a lock."""

    def __init__(self):
        self.violations = collections.deque(maxlen=256)

    def record(self, component, program):
        self.violations.append((component, program))


_ACTIVE = None
_ACTIVE_LOCK = make_lock("warmup._ACTIVE_LOCK")


def activate(sentinel: CompileSentinel) -> CompileSentinel:
    """Install `sentinel` as the process-wide witness (chaos fixture)."""
    global _ACTIVE
    with _ACTIVE_LOCK:
        _ACTIVE = sentinel
    return sentinel


def deactivate():
    global _ACTIVE
    with _ACTIVE_LOCK:
        _ACTIVE = None


def notify(component, program):
    """Called by the scheduler's timing hook on a post-ready cold build."""
    s = _ACTIVE
    if s is not None:
        s.record(component, program)


# ------------------------------------------------------------- the warmup
def serving_config_of(predictor) -> ServingConfig:
    """The lint-side ServingConfig a live continuous predictor embodies —
    the bridge between the static pass and the runtime (drift between the
    two shows up as AOTWarmup 'missing' keys, not as silence)."""
    return ServingConfig(
        name=getattr(predictor, "_component", "serving"),
        slots=predictor.max_slots,
        prefill_chunk=predictor.prefill_chunk,
        decode_steps=predictor.decode_steps,
        spec_k=predictor.spec_k,
        eos_token_id=predictor.eos_token_id,
        max_seq_len=predictor.max_seq_len,
        kv_signature=tuple(predictor.kv_cache.signature()),
        decode_kernel=predictor.decode_kernel,
        ids_dtype="int64",
        adapter_signature=(
            predictor.adapters.signature()
            if getattr(predictor, "adapters", None) is not None else None),
    )


class AOTWarmup:
    """Compile a continuous predictor's manifest programs before ready.

    run() launches each active step program once with idle inputs, then
    audits coverage: every derived cache key must be present in the
    model's runner cache afterwards. The returned stats dict is what the
    scheduler publishes through ``warm_stats()``:

        programs      manifest size for this config
        compiled      programs this run cold-built (0 on a warm restart
                      of a shared-model fleet replica)
        missing       derived keys NOT in the runner cache after warmup —
                      non-empty means static/runtime drift; the sentinel
                      does not arm (warmup_incomplete, see scheduler)
        fingerprints  {path: aval fingerprint of the warmup launch args}
                      (jit/fingerprint.py — shared with StepMonitor)
        seconds       wall time of the warmup launches
    """

    def __init__(self, predictor, *, cache_dir=None, tracer=None):
        self._pred = predictor
        self._cache_dir = cache_dir
        self._tracer = tracer

    def config(self) -> ServingConfig:
        return serving_config_of(self._pred)

    def programs(self):
        return self.config().program_keys()

    def _launch(self, path):
        """One idle-shaped launch of `path`; returns the launch args'
        aval fingerprint. Masks make these write-free: chunk_lens == 0
        drops every prefill scatter, active == False drops decode/verify
        writes, and commit() re-installs equal pools."""
        pred = self._pred
        model = pred.model
        S, W = pred.max_slots, pred.table_width
        kv, kern = pred.kv_cache, pred.decode_kernel
        tables = np.zeros((S, W), np.int32)
        zeros_i = np.zeros((S,), np.int64)
        idle = np.zeros((S,), bool)
        # LoRA-enabled predictors warm the BANKED program variant: an
        # all-slot-0 (identity) index builds the exact program every later
        # adapter mix reuses — the cache key carries only the bank shape
        ad = getattr(pred, "adapters", None)
        akw = ({} if ad is None else
               dict(adapters=ad, adapter_slots=np.zeros((S,), np.int32)))
        if path == "prefill_chunk":
            args = (np.zeros((S, pred.prefill_chunk), np.int64),
                    zeros_i, zeros_i, kv, tables)
            model.prefill_chunk(*args, eos_token_id=pred.eos_token_id,
                                decode_kernel=kern, seed=0, **akw)
        elif path == "decode_step":
            args = (zeros_i, zeros_i, idle, kv, tables)
            model.decode_step(*args, steps=pred.decode_steps,
                              eos_token_id=pred.eos_token_id,
                              decode_kernel=kern, seed=0, **akw)
        elif path == "verify_step":
            args = (np.zeros((S, pred.spec_k + 1), np.int64),
                    zeros_i, zeros_i, idle, kv, tables)
            model.verify_step(*args, decode_kernel=kern, seed=0, **akw)
        else:
            raise ValueError(f"no warmup launch for path {path!r}")
        return aval_fingerprint(args[:3], None)

    def run(self) -> dict:
        pred = self._pred
        t0 = time.perf_counter()
        tr = self._tracer
        t_us = tr.now_us() if tr is not None and tr.enabled else None
        if self._cache_dir:
            enable_compile_cache(self._cache_dir)
        cfg = self.config()
        keys = cfg.program_keys()
        cache = pred.model._runner_cache()
        before = set(cache)
        fingerprints = {}
        for path in cfg.active_paths():
            if pred._stop.is_set():     # closing mid-warmup: stop cleanly
                break
            fingerprints[path] = self._launch(path)
        after = set(pred.model._runner_cache())
        missing = [k for k in keys if k not in after]
        stats = {
            "programs": len(keys),
            "compiled": len(after - before),
            "missing": missing,
            "fingerprints": fingerprints,
            "seconds": time.perf_counter() - t0,
        }
        if t_us is not None:
            tr.record("aot_warmup", t_us, tr.now_us(), trace_id="warmup",
                      tags={"programs": stats["programs"],
                            "compiled": stats["compiled"],
                            "missing": len(missing)})
        return stats
