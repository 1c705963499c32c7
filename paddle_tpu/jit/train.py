"""TrainStep: whole-training-step compilation — the TPU performance path.

Reference parity: this replaces the reference's static-graph Executor training path
(StandaloneExecutor over a Program, SURVEY.md §3.2) — forward, backward, grad clip and
optimizer update compile into ONE XLA program, so there is no per-op dispatch and XLA
fuses/overlaps everything (including GSPMD collectives when params/batch are sharded).

Works with any Layer + loss callable + paddle_tpu optimizer: optimizer accumulator
state is lifted into the jitted function's inputs/outputs by temporarily rebinding the
optimizer's accumulator store onto tracers (parameter ids are stable, so the same
`_update` rules run traced).
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from ..autograd import tape
from ..framework import random as _rng
from .fingerprint import aval_fingerprint
from ..nn.clip import ClipGradByGlobalNorm, ClipGradByNorm, ClipGradByValue
from ..nn.layer import Layer
from ..tensor import Tensor


def _functional_clip(grad_clip, grads: dict, params: dict):
    if grad_clip is None:
        return grads
    if isinstance(grad_clip, ClipGradByGlobalNorm):
        sq = sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in grads.values())
        gnorm = jnp.sqrt(sq)
        scale = jnp.minimum(grad_clip.clip_norm / jnp.maximum(gnorm, 1e-12), 1.0)
        return {k: (g * scale).astype(g.dtype) for k, g in grads.items()}
    if isinstance(grad_clip, ClipGradByNorm):
        out = {}
        for k, g in grads.items():
            n = jnp.sqrt(jnp.sum(jnp.square(g)))
            out[k] = g * jnp.minimum(grad_clip.clip_norm / jnp.maximum(n, 1e-12), 1.0)
        return out
    if isinstance(grad_clip, ClipGradByValue):
        return {k: jnp.clip(g, grad_clip.min, grad_clip.max) for k, g in grads.items()}
    return grads


class TrainStep:
    """Compiled (loss, new_state) = step(batch).

    Usage:
        step = TrainStep(model, loss_fn, optimizer)   # loss_fn(outputs, labels)
        for x, y in loader:
            loss = step(x, y)                         # one XLA launch
    Parameter and accumulator updates are written back into the live Layer/optimizer
    objects after each call, so eval/save/load interop with the eager world.

    `in_shardings`: optional fn(name, value) -> jax sharding for params (hybrid
    parallel recipes hook in here); batch shardings via `batch_sharding`.
    """

    def __init__(self, model: Layer, loss_fn: Callable, optimizer, donate_state=True,
                 return_outputs=False, split_label=False):
        self.model = model
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        # hapi metrics need the forward outputs: thread them out of the
        # compiled step as an aux (costs an extra device->host copy per call)
        self._return_outputs = return_outputs
        # split_label=True: the LAST positional arg is always the label — for
        # callers (hapi) that know, bypassing the forward-signature heuristic
        # (which misbinds labels into optional forward params like mask=None)
        self._split_label = split_label
        self._param_tensors = dict(model.state_dict())
        self._trainable = {
            k: t for k, t in self._param_tensors.items()
            if not t.stop_gradient and jnp.issubdtype(t.dtype, jnp.floating)
        }
        self._jitted = None
        self._compiled = None  # AOT executable installed by aot_prime()
        self._compiled_avals = None  # arg shapes/dtypes the AOT exe was built for
        self._monitor = None  # observability.training.StepMonitor.bind() target
        self._pending_monitor_counters = None  # checkpoint-restored counters
        # parked for a monitor that binds after import_state (the fit path)
        self._seed = 0
        # ZeRO stage recipe (dist.shard_optimizer(opt, ShardingStage1/2/3)):
        # enforced as shardings inside the compiled step — state in, grads mid,
        # state out — so the layout lives in ONE XLA program (reduce-scatter /
        # gather-on-use emitted by GSPMD), no eager relayout round-trips.
        self._stage = getattr(optimizer, "_shard_fn", None)
        if self._stage is not None and not hasattr(self._stage, "acc_sharding"):
            self._stage = None
        if self._stage is not None:
            for k, t in self._param_tensors.items():
                sh = self._stage.param_sharding(t)
                if sh is not None:
                    t._value = jax.device_put(t._value, sh)

    # -------------------------------------------------------------- traced step
    def _build(self):
        model = self.model
        opt = self.optimizer
        loss_fn = self.loss_fn
        trainable_keys = list(self._trainable)
        param_tensors = self._param_tensors
        return_outputs = self._return_outputs
        # map param name -> live Parameter object (ids stable across calls)
        inner_opt = getattr(opt, "_inner_opt", opt)
        stage = self._stage

        import inspect

        try:
            fwd_sig = inspect.signature(type(model).forward)
        except (TypeError, ValueError):
            fwd_sig = None

        def step_fn(state, acc_state, step_i, lr, key, args, kwargs):
            # Batch-splitting convention: if the model's forward can bind every arg,
            # it gets them all (models that compute loss internally, e.g.
            # GPTForCausalLM(input_ids, labels=...)); otherwise the last positional
            # arg is the label and goes to loss_fn (classifier + CrossEntropyLoss).
            model_args, label = args, None
            if self._split_label:
                model_args, label = args[:-1], args[-1]
            elif fwd_sig is not None:
                try:
                    fwd_sig.bind(model, *args, **kwargs)
                except TypeError:
                    model_args, label = args[:-1], args[-1]

            def loss_from(trainable_state):
                full = dict(state)
                full.update(trainable_state)
                mutated: dict = {}
                with _rng.trace_key(key), tape.no_grad():
                    out = model.functional_call(
                        full, *model_args, _capture_mutations=mutated, **kwargs
                    )
                    if label is not None:
                        loss_t = loss_fn(out, label)
                    elif isinstance(out, (tuple, list)):
                        loss_t = loss_fn(*out)
                    else:
                        loss_t = loss_fn(out)
                loss_v = loss_t._value if isinstance(loss_t, Tensor) else loss_t
                # auxiliary losses set by sublayers during THIS forward (MoE
                # gate load-balance l_aux) join the objective automatically —
                # without this, a user composing GPT+MoE silently trains with
                # no load balancing (reference wires gate.get_loss() the same
                # way). Freshness check: the attr must hold a tracer from the
                # live trace, not a stale concrete value from an eager call.
                for _l in model.sublayers(include_self=True):
                    _la = getattr(_l, "l_aux", None)
                    if _la is None:
                        continue
                    _lv = _la._value if isinstance(_la, Tensor) else _la
                    if isinstance(_lv, jax.core.Tracer):
                        loss_v = loss_v + _lv.astype(loss_v.dtype)
                # buffer updates (BN running mean/var) flow out as aux so they
                # survive functional_call's state restore
                buffers = {
                    k: (v._value if isinstance(v, Tensor) else v)
                    for k, v in mutated.items() if k not in trainable_keys
                }
                outs = None
                if return_outputs:
                    outs = jax.tree.map(
                        lambda t: (jax.lax.stop_gradient(t._value)
                                   if isinstance(t, Tensor) else t),
                        out, is_leaf=lambda t: isinstance(t, Tensor))
                return loss_v, (buffers, outs)

            trainable_state = {k: state[k] for k in trainable_keys}
            (loss_val, (new_buffers, fwd_outs)), grads = jax.value_and_grad(
                loss_from, has_aux=True
            )(trainable_state)
            if stage is not None and stage.shard_grads:
                # ZeRO-2/3: constrain gradient layout to the stage axis so the
                # dp gradient all-reduce lowers to reduce-scatter
                grads = {
                    k: (jax.lax.with_sharding_constraint(g, sh)
                        if (sh := stage.grad_sharding(tuple(g.shape))) is not None
                        else g)
                    for k, g in grads.items()
                }
            grads = _functional_clip(inner_opt._grad_clip, grads,
                                     trainable_state)
            # run optimizer update rules traced: swap accumulator store
            saved_acc = inner_opt._accumulators
            saved_step = inner_opt._step_count
            new_state = dict(state)
            try:
                # rebuild accumulator store with traced values keyed by live param ids
                traced_store: dict = {}
                for acc_name, per_param in acc_state.items():
                    traced_store[acc_name] = {
                        id(param_tensors[k]): v for k, v in per_param.items()
                    }
                inner_opt._accumulators = traced_store
                inner_opt._step_count = step_i
                for k in trainable_keys:
                    p = param_tensors[k]
                    g = grads[k]
                    pval = state[k]
                    plr = lr * p.optimize_attr.get("learning_rate", 1.0) if hasattr(
                        p, "optimize_attr") else lr
                    # pin the result to the param dtype: f32 lr scalars promote bf16
                    # params to f32 otherwise, silently retracing every step
                    new_state[k] = inner_opt._update(
                        p, pval, g.astype(pval.dtype), plr
                    ).astype(pval.dtype)
                new_acc = {
                    acc_name: {
                        k: traced_store[acc_name].get(id(param_tensors[k]))
                        for k in trainable_keys
                        if id(param_tensors[k]) in traced_store[acc_name]
                    }
                    for acc_name in traced_store
                }
            finally:
                inner_opt._accumulators = saved_acc
                inner_opt._step_count = saved_step
            new_state.update(new_buffers)
            if stage is not None:
                # pin output layouts: params (stage 3: sharded; stages 1-2:
                # replicated, or XLA would propagate the acc sharding onto them)
                # and optimizer state (stages 1-3: sharded)
                from jax.sharding import NamedSharding, PartitionSpec

                stage_mesh = stage._mesh()
                for k in trainable_keys:
                    psh = stage.param_sharding(param_tensors[k])
                    if psh is None and stage_mesh is not None and getattr(
                            param_tensors[k], "_dist_attr", None) is None:
                        psh = NamedSharding(stage_mesh.jax_mesh, PartitionSpec())
                    if psh is not None:
                        new_state[k] = jax.lax.with_sharding_constraint(
                            new_state[k], psh)
                for acc_name, per in new_acc.items():
                    for k, v in per.items():
                        if v is None:
                            continue
                        ash = stage.acc_sharding(param_tensors[k], tuple(v.shape))
                        if ash is not None:
                            per[k] = jax.lax.with_sharding_constraint(v, ash)
            if return_outputs:
                return loss_val, new_state, new_acc, fwd_outs
            return loss_val, new_state, new_acc

        return jax.jit(step_fn, donate_argnums=(0, 1))

    # ---------------------------------------------------- device-side multi-step
    def _build_scan(self, stacked_flags):
        """K steps inside ONE compiled program via lax.scan — the reference's
        Plan/Job executor shape (whole schedule device-side, SURVEY §3.2), and
        the antidote to per-call host dispatch: a host->device call carries
        ~2 buffers per parameter (state + accumulators), marshalled per call
        (cost on the v5e: not measured). Stacked batches ([K, ...],
        one slice per step) ride the scan xs; reused batches are closed over
        ONCE (no K-fold host-side broadcast copy); per-step RNG keys and LRs
        are precomputed arrays so the scan body is identical to a single
        __call__'s step_fn."""
        if self._jitted is None:
            self._jitted = self._build()
        step_fn = self._jitted.__wrapped__

        def scan_fn(state, acc_state, step_is, lrs, keys, scan_args,
                    const_args, kwargs):
            def body(carry, per_step):
                state, acc_state = carry
                step_i, lr, key, sliced = per_step
                it_s, it_c = iter(sliced), iter(const_args)
                args = tuple(next(it_s) if is_stacked else next(it_c)
                             for is_stacked in stacked_flags)
                out = step_fn(state, acc_state, step_i, lr, key, args, kwargs)
                loss_val, new_state, new_acc = out[:3]
                return (new_state, new_acc), loss_val

            (new_state, new_acc), losses = jax.lax.scan(
                body, (state, acc_state), (step_is, lrs, keys, scan_args))
            return losses, new_state, new_acc

        return jax.jit(scan_fn, donate_argnums=(0, 1), static_argnums=())

    def _prep_scan_inputs(self, n_steps, args, stacked, advance):
        """Shared assembly for run_steps/lowered_steps. `advance=True` bumps
        the optimizer step counter and RNG seed (a real run); False peeks."""
        inner_opt = getattr(self.optimizer, "_inner_opt", self.optimizer)
        state = {k: t._value for k, t in self._param_tensors.items()}
        acc_state = self._gather_acc_state()
        step0, seed0 = inner_opt._step_count, self._seed
        step_is, lrs, keys = [], [], []
        for i in range(n_steps):
            step_is.append(step0 + 1 + i)
            lrs.append(inner_opt.get_lr())
            keys.append(jax.random.fold_in(_rng.default_generator().base_key(),
                                           seed0 + 1 + i))
        if advance:
            inner_opt._step_count = step0 + n_steps
            self._seed = seed0 + n_steps

        vals = tuple(a._value if isinstance(a, Tensor) else jnp.asarray(a)
                     for a in args)
        if stacked:
            for v in vals:
                if v.ndim == 0 or v.shape[0] != n_steps:
                    raise ValueError(
                        f"stacked=True: every batch arg needs leading dim "
                        f"{n_steps}, got shape {v.shape}")
        flags = tuple(bool(stacked) for _ in vals)
        scan_args = tuple(v for v, f in zip(vals, flags) if f)
        const_args = tuple(v for v, f in zip(vals, flags) if not f)
        return (inner_opt, state, acc_state,
                jnp.asarray(step_is, jnp.int32),
                jnp.asarray(lrs, jnp.float32), jnp.stack(keys),
                scan_args, const_args, flags)

    def _scanned_for(self, flags):
        cache = getattr(self, "_scan_cache", None)
        if cache is None:
            cache = self._scan_cache = {}
        fn = cache.get(flags)
        if fn is None:
            fn = cache[flags] = self._build_scan(flags)
        return fn

    def run_steps(self, n_steps: int, *args, stacked=False, **kwargs):
        """Run `n_steps` training steps in one device-side program.

        `stacked=True`: every positional batch arg carries a leading
        dim of `n_steps` — one slice per step. `stacked=False` (default):
        the same batch is reused every step (closed over in-program — no
        K-fold copy). Returns per-step losses as a Tensor [K]. Numerics match
        n_steps sequential __call__s exactly: the same step counters, LR
        values and RNG key derivations are precomputed per step.
        """
        if self._return_outputs:
            raise ValueError("run_steps does not support return_outputs=True")
        mon = self._monitor
        t0 = mon.step_begin() if mon is not None else None
        (inner_opt, state, acc_state, step_is, lrs, keys, scan_args,
         const_args, flags) = self._prep_scan_inputs(n_steps, args, stacked,
                                                     advance=True)
        if mon is not None:
            mon.before_scan_launch(self, n_steps, flags, args, kwargs, t0)
        losses, new_state, new_acc = self._scanned_for(flags)(
            state, acc_state, step_is, lrs, keys, scan_args, const_args,
            kwargs)
        for k, t in self._param_tensors.items():
            t._value = new_state[k]
        for acc_name, per in new_acc.items():
            store = inner_opt._accumulators.setdefault(acc_name, {})
            for k, v in per.items():
                store[id(self._param_tensors[k])] = v
        if mon is not None:
            mon.step_end(self, losses[-1], t0, n_steps=n_steps)
        return Tensor(losses)

    def lowered_steps(self, n_steps: int, *args, stacked=False, **kwargs):
        """AOT-lower run_steps for cost_analysis (flops are for ALL n_steps)."""
        (_, state, acc_state, step_is, lrs, keys, scan_args, const_args,
         flags) = self._prep_scan_inputs(n_steps, args, stacked, advance=False)
        return self._scanned_for(flags).lower(
            state, acc_state, step_is, lrs, keys, scan_args, const_args,
            kwargs)

    def _gather_acc_state(self):
        inner_opt = getattr(self.optimizer, "_inner_opt", self.optimizer)
        acc = {}
        for acc_name, store in inner_opt._accumulators.items():
            per = {}
            for k, t in self._param_tensors.items():
                if id(t) in store:
                    per[k] = store[id(t)]
            acc[acc_name] = per
        # materialize zero-init accumulators on first call so the traced shapes exist
        if not acc:
            names = getattr(inner_opt, "_acc_names", ())
            acc_init = getattr(inner_opt, "_acc_init",
                               lambda name, v: jnp.zeros_like(v))
            for acc_name in names:
                if acc_name == "moment2_max" and not getattr(inner_opt, "_amsgrad", False):
                    continue
                acc[acc_name] = {
                    k: acc_init(acc_name, t._value)
                    for k, t in self._trainable.items()
                }
            if self._stage is not None:
                for acc_name, per in acc.items():
                    for k, v in per.items():
                        sh = self._stage.acc_sharding(self._param_tensors[k],
                                                      tuple(v.shape))
                        if sh is not None:
                            per[k] = jax.device_put(v, sh)
        return acc

    # ------------------------------------------------- checkpoint state hooks
    def export_state(self):
        """Everything a bit-exact resume needs, as live array refs + a
        JSON-able ``meta`` — the ``framework.checkpoint.CheckpointManager``
        provider contract. Cheap (no copies): the manager host-materializes
        immediately, before the next step can donate these buffers."""
        inner_opt = getattr(self.optimizer, "_inner_opt", self.optimizer)
        state = {
            "params": {k: t._value for k, t in self._param_tensors.items()},
            "acc": self._gather_acc_state(),
        }
        mw = getattr(inner_opt, "_master_weights", None)
        if mw:
            by_id = {id(t): k for k, t in self._param_tensors.items()}
            state["master"] = {by_id[pid]: v for pid, v in mw.items()
                               if pid in by_id}
        meta = {
            "step_count": int(inner_opt._step_count),
            "seed": int(self._seed),
            "rng": list(_rng.get_rng_state()),
        }
        from ..optimizer.lr import LRScheduler

        if isinstance(inner_opt._learning_rate, LRScheduler):
            meta["lr_sched"] = inner_opt._learning_rate.state_dict()
        if self._monitor is not None:
            counters = getattr(self._monitor, "export_counters", None)
            if counters is not None:
                meta["monitor"] = counters()
        state["meta"] = meta
        return state

    def import_state(self, state):
        """Reverse of ``export_state``: rebuild params/accumulators/counters
        so the NEXT step reproduces what an uninterrupted run would have
        computed, bit for bit. Values land with the avals (shape/dtype) and
        shardings of the current state, so the cached executable (jit cache
        or AOT) is reused — restoring never recompiles."""
        inner_opt = getattr(self.optimizer, "_inner_opt", self.optimizer)
        for k, t in self._param_tensors.items():
            v = state.get("params", {}).get(k)
            if v is not None:
                t._value = self._place_like(v, t._value)
        for acc_name, per in (state.get("acc") or {}).items():
            store = inner_opt._accumulators.setdefault(acc_name, {})
            for k, v in per.items():
                t = self._param_tensors.get(k)
                if t is None:
                    continue
                cur = store.get(id(t))
                val = self._place_like(v, cur)
                if self._stage is not None:
                    sh = self._stage.acc_sharding(t, tuple(val.shape))
                    if sh is not None:
                        val = jax.device_put(val, sh)
                store[id(t)] = val
        if state.get("master"):
            mw = getattr(inner_opt, "_master_weights", None)
            if mw is not None:
                for k, v in state["master"].items():
                    t = self._param_tensors.get(k)
                    if t is not None:
                        mw[id(t)] = self._place_like(v, mw.get(id(t)))
        meta = state.get("meta") or {}
        if "step_count" in meta:
            inner_opt._step_count = int(meta["step_count"])
        if "seed" in meta:
            self._seed = int(meta["seed"])
        if "rng" in meta:
            _rng.set_rng_state(tuple(meta["rng"]))
        if "lr_sched" in meta:
            from ..optimizer.lr import LRScheduler

            if isinstance(inner_opt._learning_rate, LRScheduler):
                inner_opt._learning_rate.set_state_dict(meta["lr_sched"])
        if "monitor" in meta:
            if self._monitor is not None:
                importer = getattr(self._monitor, "import_counters", None)
                if importer is not None:
                    importer(meta["monitor"])
            else:
                # no monitor bound yet (fit binds via MonitorCallback on the
                # first batch, AFTER restore): park the counters for bind()
                self._pending_monitor_counters = dict(meta["monitor"])

    @staticmethod
    def _place_like(value, current):
        """Device-place a restored array with the dtype/sharding of the live
        value it replaces — the aval must not change or the next launch
        retraces (the recompile sentinel pins this in tests)."""
        if current is None:
            return jnp.asarray(value)
        dtype = getattr(current, "dtype", None)
        arr = np.asarray(value) if not isinstance(value, jax.Array) else value
        if dtype is not None and arr.dtype != dtype:
            arr = arr.astype(dtype)
        if isinstance(current, jax.Array) and not isinstance(
                current, jax.core.Tracer):
            try:
                return jax.device_put(arr, current.sharding)
            except Exception:  # pragma: no cover - exotic placement
                pass
        return jnp.asarray(arr)

    def _prep_inputs(self, advance: bool):
        """Build the exact traced-input tuple a step consumes. `advance=True` bumps
        the step counter / RNG seed (a real step); `advance=False` peeks at what the
        NEXT call would pass (AOT lowering for audit), mutating nothing."""
        if self._jitted is None:
            self._jitted = self._build()
        inner_opt = getattr(self.optimizer, "_inner_opt", self.optimizer)
        state = {k: t._value for k, t in self._param_tensors.items()}
        acc_state = self._gather_acc_state()
        if advance:
            inner_opt._step_count += 1
            self._seed += 1
            seed, step_count = self._seed, inner_opt._step_count
        else:
            seed, step_count = self._seed + 1, inner_opt._step_count + 1
        key = jax.random.fold_in(_rng.default_generator().base_key(), seed)
        step_i = jnp.asarray(step_count, jnp.int32)
        lr = jnp.asarray(inner_opt.get_lr(), jnp.float32)
        return inner_opt, (state, acc_state, step_i, lr, key)

    def lowered(self, *args, **kwargs):
        """AOT-lower the compiled step for the same (args, kwargs) a __call__ would
        see — for `compile().cost_analysis()` (FLOPs/MFU audit) without executing a
        step or mutating optimizer bookkeeping."""
        _, traced = self._prep_inputs(advance=False)
        return self._jitted.lower(*traced, args, kwargs)

    def aot_prime(self, *args, **kwargs):
        """Compile once ahead-of-time and install the executable so subsequent
        __call__s reuse it (avoids the separate jit-cache compile). Returns the
        jax compiled object (cost_analysis(), as_text())."""
        self._compiled = self.lowered(*args, **kwargs).compile()
        self._compiled_avals = self._arg_avals(args, kwargs)
        return self._compiled

    # one fingerprint definition shared with the serving warmup/sentinel
    # (jit/fingerprint.py) so the two recompile sentinels cannot drift
    _arg_avals = staticmethod(aval_fingerprint)

    def __call__(self, *args, **kwargs):
        mon = self._monitor
        t0 = mon.step_begin() if mon is not None else None
        inner_opt, traced = self._prep_inputs(advance=True)
        fn = self._jitted
        aot_hit = False
        if self._compiled is not None:
            # the AOT executable is shape-specialised; a different batch shape
            # must fall back to the jitted path (which recompiles) not raise
            if self._arg_avals(args, kwargs) == self._compiled_avals:
                fn = self._compiled
                aot_hit = True
        if mon is not None:
            # h2d span closes + recompile sentinel fingerprints the avals
            # (catching the aot-fallback recompile right above)
            mon.before_launch(self, args, kwargs, aot_hit, t0)
        result = fn(*traced, args, kwargs)
        if self._return_outputs:
            loss_val, new_state, new_acc, fwd_outs = result
        else:
            (loss_val, new_state, new_acc), fwd_outs = result, None
        # write back into live objects
        for k, t in self._param_tensors.items():
            t._value = new_state[k]
        for acc_name, per in new_acc.items():
            store = inner_opt._accumulators.setdefault(acc_name, {})
            for k, v in per.items():
                store[id(self._param_tensors[k])] = v
        if mon is not None:
            mon.step_end(self, loss_val, t0)
        if self._return_outputs:
            outs = jax.tree.map(Tensor, fwd_outs)
            return Tensor(loss_val), outs
        return Tensor(loss_val)
