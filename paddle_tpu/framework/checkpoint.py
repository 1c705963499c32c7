"""Preemption-tolerant training checkpoints: async, sharded, bit-exact.

Production TPU fleets get preempted; a training run must treat that as
routine (ROADMAP open item 5). This module owns the on-disk checkpoint
lifecycle for full ``jit/train.py:TrainStep`` state — params, optimizer
moments, step counter, RNG state, monitor counters — with three properties
the simpler ``io_utils.save`` path cannot give:

1. **Asynchrony.** ``save()`` splits into three phases. *snapshot* runs on
   the caller thread right after a step: device→host transfers are kicked
   off for every array at once (``copy_to_host_async``) and materialized
   into a host tree — this MUST finish before the next step launches,
   because TrainStep donates its state buffers and a later read would find
   them deleted. *serialize* (npz write + fsync) and *commit* (manifest +
   atomic rename + retention) then run on a background writer thread,
   overlapped with the next steps' compute. Only the snapshot cost lands on
   the training loop (its share of a step on the chip: not measured).

2. **Crash-atomicity.** Each checkpoint is a step-numbered directory,
   assembled under a ``.tmp`` name and renamed into place only after every
   data file is fsynced and the manifest — written last, itself via
   tmp+rename — records each file's size and crc32. A kill at ANY point
   leaves either a complete checkpoint or ignorable debris; ``restore()``
   walks manifests newest-first, verifies integrity, and falls back to the
   previous intact checkpoint on corruption with a typed
   ``CheckpointCorruptWarning`` — it never crashes on torn state.

3. **Sharding.** Every process writes only its own replica-0 shards
   (``data_r{rank}.npz``, the ``distributed/checkpoint`` chunk format); the
   coordinator collates per-rank sidecars into the manifest. Restore is
   mesh-aware: chunks are stitched through ``ChunkReader`` against each
   array's CURRENT sharding, so a run can resume on a different process
   count than it saved with (shared-filesystem checkpoints, the TPU-pod
   norm).

Fault drills: with an ``inference/faults.py`` injector attached, the sites
``ckpt.snapshot`` / ``ckpt.serialize`` / ``ckpt.commit`` are checked at each
phase entry and all timing reads go through the injector's skewable clock —
the kill/resume suite in tests/test_checkpoint.py is deterministic, not
probabilistic. Goodput accounting rides the bound ``StepMonitor``
(``paddle_train_goodput``, ``paddle_train_checkpoint_seconds{phase}``,
``paddle_train_checkpoints_total``); recipes in docs/DEPLOYMENT.md
("Preemption & resume") and docs/OBSERVABILITY.md.
"""
from __future__ import annotations

import json
import os
import queue
import shutil
import threading
import time
import warnings
import zlib

import numpy as np

from ..analysis.lockwitness import make_lock
from .io_utils import fsync_dir, fsync_file

__all__ = ["CheckpointManager", "CheckpointCorruptWarning", "latest_step",
           "PreemptionFlush", "PreemptionExit"]

_MANIFEST = "manifest.json"
_STEP_PREFIX = "step_"
_TMP_SUFFIX = ".tmp"


class CheckpointCorruptWarning(UserWarning):
    """A checkpoint directory failed integrity validation (torn manifest,
    missing/truncated/corrupt shard). The manager falls back to the previous
    intact checkpoint instead of crashing — but the operator should know."""


class PreemptionExit(SystemExit):
    """Raised by the training loop after a SIGTERM-triggered final flush.

    Subclasses SystemExit carrying ``ELASTIC_EXIT_CODE`` (101), so an
    un-caught preemption exits the worker process with the code the elastic
    launch controller treats as "restart me, this is not a crash" — the
    same contract the legacy ``AutoCheckpointer`` spoke, now available to
    every ``CheckpointManager``-checkpointed fit loop."""


class PreemptionFlush:
    """SIGTERM -> flag; the training loop polls and flushes synchronously.

    Pod preemption lands as SIGTERM with a grace window (the elastic launch
    controller's ``stop_pod`` sends exactly that). The handler itself must
    not serialize state — the signal can land mid-optimizer-update — so it
    only sets ``preempted``; the fit loop checks the flag at the next batch
    boundary, takes a final SYNCHRONOUS ``CheckpointManager.save`` of
    well-formed post-step state, and raises :class:`PreemptionExit`.

    ``install()`` is a no-op outside the main thread (Python only delivers
    signals there) and chains nothing: the previous handler is restored by
    ``restore()`` in the fit loop's ``finally``."""

    def __init__(self):
        self.preempted = False
        self.installed = False
        self._prev = None

    def install(self) -> "PreemptionFlush":
        import signal

        try:
            self._prev = signal.signal(signal.SIGTERM, self._on_sigterm)
            self.installed = True
        except ValueError:      # not the main thread: poll-only mode
            self.installed = False
        return self

    def _on_sigterm(self, signum, frame):
        self.preempted = True

    def restore(self):
        if not self.installed:
            return
        import signal

        signal.signal(signal.SIGTERM, self._prev or signal.SIG_DFL)
        self.installed = False

    @staticmethod
    def exit_code() -> int:
        from ..distributed.fleet.elastic.manager import ELASTIC_EXIT_CODE

        return ELASTIC_EXIT_CODE


def _step_dirname(step):
    return f"{_STEP_PREFIX}{int(step):010d}"


def _parse_step(name):
    if not name.startswith(_STEP_PREFIX) or name.endswith(_TMP_SUFFIX):
        return None
    try:
        return int(name[len(_STEP_PREFIX):])
    except ValueError:
        return None


def latest_step(directory):
    """Highest step number with a manifest present (cheap discovery; full
    integrity validation happens in ``restore``). None when none exist."""
    best = None
    try:
        names = os.listdir(directory)
    except OSError:
        return None
    for name in names:
        step = _parse_step(name)
        if step is None:
            continue
        if not os.path.exists(os.path.join(directory, name, _MANIFEST)):
            continue
        if best is None or step > best:
            best = step
    return best


def _flatten(tree, prefix=""):
    flat = {}
    for k, v in tree.items():
        name = f"{prefix}{k}"
        if isinstance(v, dict):
            flat.update(_flatten(v, prefix=f"{name}."))
        else:
            flat[name] = v
    return flat


def _crc_file(path, chunk=1 << 20):
    crc = 0
    with open(path, "rb") as f:
        while True:
            buf = f.read(chunk)
            if not buf:
                break
            crc = zlib.crc32(buf, crc)
    return crc & 0xFFFFFFFF


class _CorruptCheckpoint(Exception):
    """Internal: validation failure reason (becomes the warning message)."""


class CheckpointManager:
    """Async sharded save / mesh-aware restore of TrainStep training state.

    Usage (bare loop)::

        mgr = CheckpointManager(ckpt_dir, keep_last=3, keep_every=100)
        start = mgr.restore(step) or 0          # step = TrainStep(...)
        for i in range(start, total):
            loss = step(x, labels=y)
            if (i + 1) % save_every == 0:
                mgr.save(step, i + 1)           # snapshot now, write async
        mgr.save(step, total)
        mgr.close()                             # drain pending writes

    ``Model.fit(checkpoint_dir=...)`` wires this up automatically.

    The state provider contract is two methods: ``export_state()`` returning
    ``{"params": {...}, "acc": {...}, ["master": {...}], "meta": {...}}``
    with array leaves (jax or numpy) and a JSON-able ``meta``, and
    ``import_state(state)`` accepting the same shape back with numpy/jax
    leaves. ``jit/train.py:TrainStep`` implements it; anything else (an
    eager loop's shuttle object) can too.

    * ``keep_last`` — newest N checkpoints retained (0/None = keep all).
    * ``keep_every`` — additionally retain every checkpoint whose step is a
      multiple of M (milestones survive the sliding window).
    * ``async_save`` — False serializes+commits on the caller thread
      (useful under test and for a final synchronous flush).
    * ``monitor`` — a ``StepMonitor``; phase timings feed
      ``paddle_train_checkpoint_seconds{phase}`` and commit/restore feed the
      goodput window. Reassignable at any time (fit binds it lazily).
    * ``injector`` — ``inference/faults.py:FaultInjector`` for deterministic
      kill/skew drills at the ``ckpt.*`` sites.
    """

    def __init__(self, directory, *, keep_last=3, keep_every=0,
                 async_save=True, rank=None, world_size=None, monitor=None,
                 injector=None):
        self.directory = str(directory)
        self.keep_last = None if not keep_last else int(keep_last)
        self.keep_every = int(keep_every or 0)
        self.async_save = bool(async_save)
        if rank is None or world_size is None:
            try:
                from ..distributed.env import get_rank, get_world_size

                rank = get_rank() if rank is None else rank
                world_size = (get_world_size() if world_size is None
                              else world_size)
            except Exception:
                rank, world_size = rank or 0, world_size or 1
        self.rank = int(rank)
        self.world_size = max(1, int(world_size))
        self.monitor = monitor
        self.injector = injector
        self.last_timings: dict = {}   # phase -> seconds, last finished save
        self.saves = 0                 # snapshots taken
        self.commits = 0               # manifests landed (this process)
        self.last_restored = None      # {"step", "dir", "meta"} after restore
        self._q: queue.Queue = queue.Queue(maxsize=1)
        self._writer = None
        self._writer_err = None
        # one lock guards writer lifecycle AND the cross-thread scalars
        # (saves/commits/last_timings/_writer_err) — thread-lint discipline
        self._lock = make_lock("checkpoint.CheckpointManager._lock")
        os.makedirs(self.directory, exist_ok=True)

    # ----------------------------------------------------------------- clock
    def _now(self):
        inj = self.injector
        return inj.monotonic() if inj is not None else time.monotonic()

    def _check(self, site):
        inj = self.injector
        if inj is not None:
            inj.check(site)

    def _phase(self, phase, seconds):
        with self._lock:    # caller thread (snapshot) and writer both land
            self.last_timings[phase] = seconds
        mon = self.monitor  # monitor has its own locking; call outside ours
        if mon is not None:
            mon.checkpoint_phase(phase, seconds)

    # ------------------------------------------------------------------ save
    def save(self, provider, step, blocking=None):
        """Snapshot `provider` state at optimizer-step `step` and hand it to
        the writer. Returns the final checkpoint directory path (which exists
        only after the async commit lands — ``wait()`` to join)."""
        self._raise_writer_error()
        t0 = self._now()
        self._check("ckpt.snapshot")
        snap = provider.export_state()
        chunks, entries = self._snapshot(snap)
        meta = dict(snap.get("meta") or {})
        self._phase("snapshot", self._now() - t0)
        with self._lock:
            self.saves += 1
        job = {"step": int(step), "chunks": chunks, "entries": entries,
               "meta": meta}
        if blocking is None:
            blocking = not self.async_save
        if blocking:
            self._write(job)
        else:
            self._ensure_writer()
            self._q.put(job)   # maxsize=1: a third save blocks until the
            # in-flight write drains (bounds host memory to 2 snapshots)
        return os.path.join(self.directory, _step_dirname(step))

    def _snapshot(self, snap):
        """Host-materialize every array leaf into per-rank chunk arrays +
        manifest entries. Transfers for ALL arrays are kicked off before the
        first blocking read so D2H pipelines; the result is pure numpy — safe
        against the next step donating the device buffers."""
        import jax

        from ..distributed.checkpoint import _index_to_offsets, storable_view

        flat = {k: v for k, v in _flatten(snap).items()
                if not k.startswith("meta.")}
        for v in flat.values():
            if isinstance(v, jax.Array) and hasattr(v, "copy_to_host_async"):
                try:
                    v.copy_to_host_async()
                except Exception:   # pragma: no cover - backend-specific
                    pass
        chunks, entries = {}, {}
        for name, v in flat.items():
            if v is None or isinstance(v, (int, float, str, bool)):
                entries[name] = {"kind": "scalar", "value": v}
                continue
            if isinstance(v, jax.Array) and len(
                    getattr(v, "sharding", None).device_set
                    if getattr(v, "sharding", None) is not None else ()) > 1:
                entry = {"kind": "tensor", "shape": list(v.shape),
                         "dtype": str(np.dtype(v.dtype)), "chunks": []}
                seen = set()
                for shard in v.addressable_shards:
                    if shard.replica_id != 0:
                        continue   # exactly one replica saves each region
                    offset, cshape = _index_to_offsets(shard.index, v.shape)
                    if tuple(offset) in seen:
                        continue
                    seen.add(tuple(offset))
                    cname = f"{name}/{len(entry['chunks'])}"
                    chunks[cname] = storable_view(np.asarray(shard.data))
                    entry["chunks"].append(
                        {"offset": offset, "shape": cshape,
                         "file": self._data_name(), "key": cname})
                entries[name] = entry
                continue
            arr = np.asarray(v)
            entries[name] = {"kind": "tensor", "shape": list(arr.shape),
                             "dtype": str(arr.dtype), "chunks": []}
            if self.rank == 0:   # replicated single-device value: rank 0 owns
                cname = f"{name}/0"
                chunks[cname] = storable_view(arr)
                entries[name]["chunks"].append(
                    {"offset": [0] * arr.ndim, "shape": list(arr.shape),
                     "file": self._data_name(), "key": cname})
        return chunks, entries

    def _data_name(self):
        return f"data_r{self.rank}.npz"

    # ---------------------------------------------------------- writer thread
    def _ensure_writer(self):
        with self._lock:
            if self._writer is not None and self._writer.is_alive():
                return
            self._writer = threading.Thread(
                target=self._writer_loop, name="ckpt-writer", daemon=True)
            self._writer.start()

    def _writer_loop(self):
        while True:
            job = self._q.get()
            if job is None:
                self._q.task_done()   # keep q.join() sound after close()
                return
            try:
                self._write(job)
            except BaseException as e:   # surfaced on next save()/wait()
                with self._lock:
                    self._writer_err = e
            finally:
                self._q.task_done()

    def _raise_writer_error(self):
        with self._lock:
            err, self._writer_err = self._writer_err, None
        if err is not None:
            mon = self.monitor
            if mon is not None:
                mon.checkpoint_result(ok=False)
            raise RuntimeError(
                f"async checkpoint write failed: {err!r}") from err

    def wait(self, timeout=None):
        """Join all pending async writes; re-raises a writer failure."""
        if self._writer is not None and self._writer.is_alive():
            self._q.join()
        self._raise_writer_error()

    def close(self):
        """Drain pending writes and stop the writer thread."""
        self.wait()
        with self._lock:
            w, self._writer = self._writer, None
        if w is not None and w.is_alive():
            self._q.put(None)
            w.join(timeout=5.0)

    # ----------------------------------------------------------------- write
    def _tmp_dir(self, step):
        # shared across ranks by construction: every rank assembles into the
        # SAME .tmp dir; the coordinator renames it once complete
        return os.path.join(self.directory, _step_dirname(step) + _TMP_SUFFIX)

    def _write(self, job):
        step = job["step"]
        tmp = self._tmp_dir(step)
        final = os.path.join(self.directory, _step_dirname(step))
        t0 = self._now()
        self._check("ckpt.serialize")
        os.makedirs(tmp, exist_ok=True)
        data_path = os.path.join(tmp, self._data_name())
        if job["chunks"]:
            with open(data_path, "wb") as f:
                np.savez(f, **job["chunks"])
                fsync_file(f)
        files = {}
        if os.path.exists(data_path):
            files[self._data_name()] = {
                "bytes": os.path.getsize(data_path),
                "crc32": _crc_file(data_path)}
        sidecar = {"rank": self.rank, "keys": job["entries"], "files": files}
        sc_path = os.path.join(tmp, f"meta_r{self.rank}.json")
        with open(sc_path + ".w", "w") as f:
            json.dump(sidecar, f)
            fsync_file(f)
        os.replace(sc_path + ".w", sc_path)
        self._phase("serialize", self._now() - t0)

        t0 = self._now()
        self._check("ckpt.commit")
        if self.rank == 0:
            self._commit(step, tmp, final, job["meta"])
            self._phase("commit", self._now() - t0)
            with self._lock:
                self.commits += 1
            mon = self.monitor
            if mon is not None:
                mon.checkpoint_result(ok=True, step=step)
            self._retain()

    def _commit(self, step, tmp, final, meta, timeout=120.0):
        """Coordinator: wait for every rank's sidecar, collate the manifest,
        fsync, and atomically rename the directory into place. The manifest
        is the commit record — a directory without one is torn by definition
        and ignored at restore."""
        deadline = self._now() + timeout    # injectable (skewable) clock
        while True:
            sidecars = [n for n in os.listdir(tmp)
                        if n.startswith("meta_r") and n.endswith(".json")]
            if len(sidecars) >= self.world_size:
                break
            if self._now() > deadline:
                raise TimeoutError(
                    f"checkpoint step {step}: {len(sidecars)}/"
                    f"{self.world_size} rank sidecars within {timeout}s — "
                    "refusing to commit an incomplete checkpoint")
            time.sleep(0.05)
        keys, files = {}, {}
        for name in sorted(sidecars):
            with open(os.path.join(tmp, name)) as f:
                part = json.load(f)
            files.update(part.get("files", {}))
            for key, entry in part["keys"].items():
                if key not in keys:
                    keys[key] = entry
                elif entry.get("kind") == "tensor":
                    have = {tuple(c["offset"]) for c in keys[key]["chunks"]}
                    for c in entry["chunks"]:
                        if tuple(c["offset"]) not in have:
                            keys[key]["chunks"].append(c)
        manifest = {"version": 1, "step": int(step),
                    "world_size": self.world_size,
                    "wall_time": time.time(),   # informational ONLY —
                    # discovery orders by step number, never by clock
                    "meta": meta, "keys": keys, "files": files}
        mpath = os.path.join(tmp, _MANIFEST)
        with open(mpath + ".w", "w") as f:
            json.dump(manifest, f)
            fsync_file(f)
        os.replace(mpath + ".w", mpath)
        fsync_dir(tmp)
        if os.path.isdir(final):   # a re-save of the same step replaces it
            shutil.rmtree(final)
        os.replace(tmp, final)
        fsync_dir(self.directory)

    # ------------------------------------------------------------- retention
    def _retain(self):
        """keep-last-N + keep-every-M sweep, plus stale .tmp debris from
        previous incarnations (anything not the newest tmp)."""
        steps = []
        for name in os.listdir(self.directory):
            step = _parse_step(name)
            if step is not None:
                steps.append(step)
        steps.sort()
        keep = set(steps[-self.keep_last:] if self.keep_last else steps)
        if self.keep_every > 0:
            keep.update(s for s in steps if s % self.keep_every == 0)
        for s in steps:
            if s not in keep:
                shutil.rmtree(
                    os.path.join(self.directory, _step_dirname(s)),
                    ignore_errors=True)
        newest = steps[-1] if steps else None
        for name in os.listdir(self.directory):
            if not name.endswith(_TMP_SUFFIX):
                continue
            step = _parse_step(name[:-len(_TMP_SUFFIX)])
            # a torn tmp dir older than the newest committed step can never
            # complete (its writer is gone) — debris
            if step is not None and newest is not None and step <= newest:
                shutil.rmtree(os.path.join(self.directory, name),
                              ignore_errors=True)

    # --------------------------------------------------------------- restore
    def steps(self):
        """Committed (manifest-bearing) step numbers, ascending."""
        out = []
        try:
            names = os.listdir(self.directory)
        except OSError:
            return out
        for name in names:
            step = _parse_step(name)
            if step is not None and os.path.exists(
                    os.path.join(self.directory, name, _MANIFEST)):
                out.append(step)
        return sorted(out)

    def latest_step(self):
        steps = self.steps()
        return steps[-1] if steps else None

    def _validate(self, d):
        """Load + integrity-check a checkpoint dir's manifest; raises
        _CorruptCheckpoint with the reason on any failure."""
        mpath = os.path.join(d, _MANIFEST)
        try:
            with open(mpath) as f:
                manifest = json.load(f)
        except (OSError, ValueError) as e:
            raise _CorruptCheckpoint(f"unreadable manifest: {e!r}")
        for fname, info in manifest.get("files", {}).items():
            fpath = os.path.join(d, fname)
            if not os.path.exists(fpath):
                raise _CorruptCheckpoint(f"missing shard file {fname}")
            size = os.path.getsize(fpath)
            if size != info.get("bytes"):
                raise _CorruptCheckpoint(
                    f"shard {fname}: {size} bytes, manifest says "
                    f"{info.get('bytes')} (truncated write?)")
            if _crc_file(fpath) != info.get("crc32"):
                raise _CorruptCheckpoint(f"shard {fname}: crc32 mismatch")
        return manifest

    def restore(self, provider, step=None):
        """Discover the newest complete checkpoint (or exactly `step`),
        rebuild provider state on the current mesh, and return the restored
        step number — or None when no intact checkpoint exists. Corrupt or
        torn directories are skipped with a CheckpointCorruptWarning."""
        t0 = self._now()
        candidates = self.steps()
        if step is not None:
            candidates = [s for s in candidates if s == int(step)]
        for s in sorted(candidates, reverse=True):
            d = os.path.join(self.directory, _step_dirname(s))
            try:
                manifest = self._validate(d)
            except _CorruptCheckpoint as e:
                warnings.warn(
                    f"checkpoint {d} failed validation ({e}); falling back "
                    f"to the previous manifest", CheckpointCorruptWarning)
                continue
            state = self._read_state(d, manifest, provider)
            provider.import_state(state)
            with self._lock:
                self.last_restored = {"step": s, "dir": d,
                                      "meta": manifest.get("meta", {})}
            dt = self._now() - t0
            self._phase("restore", dt)
            return s
        return None

    def _read_state(self, d, manifest, provider):
        """Manifest entries -> the provider's nested state shape, each array
        stitched from chunks against the CURRENT sharding of the provider's
        live value (mesh-aware: a different process count than at save time
        just reads different slices off the shared filesystem)."""
        from ..distributed.checkpoint import ChunkReader

        keys = manifest["keys"]
        # walk the provider's CURRENT state shape (not the flat key strings:
        # parameter names legitimately contain dots) so every target leaf is
        # matched to its manifest entry and its live value's sharding
        template = {k: v for k, v in provider.export_state().items()
                    if k != "meta"}
        reader = ChunkReader(d)

        def fill(node, prefix):
            out = {}
            for k, v in node.items():
                name = f"{prefix}{k}"
                if isinstance(v, dict):
                    out[k] = fill(v, f"{name}.")
                    continue
                entry = keys.get(name)
                if entry is None:
                    raise ValueError(
                        f"checkpoint {d} has no entry for {name!r} — "
                        "restoring into a different model/optimizer?")
                if entry["kind"] == "scalar":
                    out[k] = entry["value"]
                else:
                    out[k] = self._read_entry(reader, entry, v)
            return out

        try:
            state = fill(template, "")
        finally:
            reader.close()
        state["meta"] = dict(manifest.get("meta") or {})
        return state

    @staticmethod
    def _read_entry(reader, entry, like):
        import jax

        shape = tuple(entry["shape"])
        full = tuple(slice(None) for _ in shape)
        if isinstance(like, jax.Array) and not isinstance(
                like, jax.core.Tracer) and tuple(like.shape) == shape:
            sharding = like.sharding
            try:
                return jax.make_array_from_callback(
                    shape, sharding,
                    lambda idx, e=entry: reader.read(e, idx))
            except Exception:   # exotic sharding: fall through to full read
                pass
        return reader.read(entry, full)
