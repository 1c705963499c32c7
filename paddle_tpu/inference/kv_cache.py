"""Block-paged KV cache for the serving layer (PagedAttention-style).

Reference role: paddle/phi/kernels/fusion/gpu/block_multi_head_attention_
kernel.cu + the BlockManager half of vLLM's design (Kwon et al., SOSP 2023).
TPU-native shape: one shared per-layer page pool on device ([num_blocks,
block_size, Hkv * D]); each request owns a block TABLE (host ints) handed to
the paged decode-attention kernel (ops/pallas/decode_attention.py), which
DMAs each request's live pages through its scalar-prefetched table row — no
gather materialization, and nothing read past a request's length. Mixed-length requests in a batch therefore hold
ceil(len/block_size) blocks each instead of every request padding to the
server-wide max length.

Host side (this file) is pure bookkeeping: a free-list allocator with LIFO
reuse (hot pages stay hot), per-request tables/lengths, and LRU eviction of
finished-but-retained requests when the pool runs dry.
"""
from __future__ import annotations

import itertools
import math

import numpy as np

from ..analysis.lockwitness import make_rlock
# the format lives below the models that declare it; re-exported here
from ..nn.functional.cached_attention import CacheSpec, LayerCache

__all__ = ["CacheOutOfBlocks", "BlockAllocator", "PagedKVCache",
           "LayerCache", "CacheSpec"]


class CacheOutOfBlocks(RuntimeError):
    """The pool cannot satisfy an allocation even after eviction."""


class BlockAllocator:
    """Fixed-population free-list block allocator.

    LIFO reuse: the most recently freed block is handed out first, so a busy
    serving loop keeps touching the same hot pages instead of sweeping the
    whole pool."""

    def __init__(self, num_blocks: int, faults=None):
        self.num_blocks = int(num_blocks)
        self._free = list(range(self.num_blocks - 1, -1, -1))
        self._live: set[int] = set()
        self._faults = faults  # inference.faults.FaultInjector | None

    @property
    def available(self) -> int:
        return len(self._free)

    @property
    def in_use(self) -> int:
        return self.num_blocks - len(self._free)

    def allocate(self, n: int) -> list[int]:
        if self._faults is not None:
            self._faults.check("kv.allocate")   # may raise CacheOutOfBlocks
        if n > len(self._free):
            raise CacheOutOfBlocks(
                f"need {n} blocks, {len(self._free)} free of {self.num_blocks}")
        out = self._free[-n:][::-1]
        del self._free[len(self._free) - n:]
        self._live.update(out)
        return out

    def free(self, blocks) -> None:
        blocks = list(blocks)
        for b in blocks:
            if not (0 <= b < self.num_blocks):
                raise ValueError(f"block {b} outside pool")
            if b not in self._live:
                raise ValueError(f"double free of block {b} (not live)")
        self._live.difference_update(blocks)
        self._free.extend(blocks)


class _Request:
    __slots__ = ("blocks", "length", "done", "touch")

    def __init__(self, blocks, length, touch):
        self.blocks = blocks
        self.length = length
        self.done = False
        self.touch = touch


class PagedKVCache:
    """Shared device page pool + per-request block tables.

    The pools are plain jax arrays (functional): a compiled decode program
    takes them as inputs and returns the updated pools, which the caller
    stores back via commit() — the same discipline TrainStep uses for
    parameters. Everything else (tables, lengths, eviction) is host state.
    """

    def __init__(self, num_layers=None, num_kv_heads=None, head_dim=None,
                 block_size=128, num_blocks=64, dtype="bfloat16", faults=None,
                 mesh=None, spec=None, slots=None, launch_rows=1):
        import jax.numpy as jnp

        if spec is None:
            spec = CacheSpec.uniform(num_layers, num_kv_heads, head_dim)
        self.spec = spec
        self.num_layers = len(spec.layers)
        # (kv_heads, head_dim) where the layers are alike K,V rows, else 0:
        # the debug `gather` and the tp head-sharding read them
        _, self.num_kv_heads, self.head_dim = spec.signature_head()
        self.block_size = int(block_size)
        self.num_blocks = int(num_blocks)
        self.dtype = jnp.dtype(dtype)
        self.slots = None if slots is None else int(slots)
        self.launch_rows = int(launch_rows)
        self.k_pages, self.v_pages = [], []
        for cache in spec.layers:
            k, v = self._layer_arrays(cache)
            self.k_pages.append(k)
            self.v_pages.append(v)
        # ("dp","tp") serving mesh: head-shard the pools over tp so each chip
        # resident-holds 1/tp of the KV bytes; step programs keep the layout
        # (commit() stores jit outputs whose shardings propagate from these)
        self.tp_sharded = False
        if mesh is None:
            from ..distributed.mesh import get_mesh
            mesh = get_mesh()
        jm = getattr(mesh, "jax_mesh", mesh)  # ProcessMesh | jax Mesh | None
        if (jm is not None and "tp" in getattr(jm, "axis_names", ())
                and spec.is_uniform_kv()):
            from ..distributed.mesh import SpecLayout, mesh_axis_size
            tp = mesh_axis_size("tp", jm)
            if tp > 1 and self.num_kv_heads % tp == 0:
                import jax
                from jax.sharding import NamedSharding, PartitionSpec
                sh = NamedSharding(jm, PartitionSpec(*SpecLayout().kv_pool()))
                self.k_pages = [jax.device_put(p, sh) for p in self.k_pages]
                self.v_pages = [jax.device_put(p, sh) for p in self.v_pages]
                self.tp_sharded = True
        self.allocator = BlockAllocator(self.num_blocks, faults=faults)
        self._requests: dict = {}
        self._clock = itertools.count()
        self._faults = faults
        self.evictions = 0          # finished-but-retained requests reclaimed
        self.evicted_blocks = 0     # blocks those evictions returned
        # per-block holder counts: how many request tables reference each
        # allocated block. Without a prefix cache every count is exactly 1
        # and the pre-sharing semantics are unchanged; with one attached,
        # reserve(shared=...) bumps counts and release only frees at zero.
        self._block_refs: dict[int, int] = {}
        self._prefix = None         # PrefixCache | None (attach_prefix_cache)
        # host bookkeeping is hit from HTTP handler threads (admission
        # checks), the batcher thread (reserve/release), and clients
        # (gather); RLock because reserve -> _evict_lru -> release re-enters
        self._lock = make_rlock("kv_cache.PagedKVCache._lock")

    @classmethod
    def for_model(cls, model, **kwargs):
        """The pool of `model._decode_cache_spec()`: the one place that
        spec is read for a pool."""
        return cls(spec=model._decode_cache_spec(), **kwargs)

    def _layer_arrays(self, cache):
        """(first, second) array of one layer: K and V pages [P, BS, Hkv*D],
        head h in lanes h*D .. (h+1)*D of a row (the one layout the row
        writer, the step programs' carry and the paged kernel share: a page
        is one contiguous block, and nothing copies the pool); latent rows
        [P, BS, row] beside the indexer's keys [P, BS, index_row] or None;
        a window layer's ring [slots, ring rows, row], no second array."""
        import jax.numpy as jnp

        if cache.kind == "kv":
            shape = (self.num_blocks, self.block_size,
                     cache.heads * cache.head_dim)
            return jnp.zeros(shape, self.dtype), jnp.zeros(shape, self.dtype)
        if cache.window is not None:
            if self.slots is None:
                raise ValueError("a window layer's ring is sized by the "
                                 "slots: pass slots=")
            ring = self.spec.ring_rows(cache, self.block_size,
                                       self.launch_rows)
            return jnp.zeros((self.slots, ring, cache.row), self.dtype), None
        pages = (self.num_blocks, self.block_size)
        index = (jnp.zeros(pages + (cache.index_row,), self.dtype)
                 if cache.index_row else None)
        return jnp.zeros(pages + (cache.row,), self.dtype), index

    def _arrays(self):
        return [p for p in self.k_pages + self.v_pages if p is not None]

    # ------------------------------------------------------------- identity
    def signature(self):
        """Hashable shape identity for compiled-runner cache keys: (layers,
        kv_heads, head_dim, block_size, num_blocks, dtype) for K,V layers
        that are alike; any other spec stands in the first place itself,
        with the slots and launch rows its rings were sized by behind."""
        head = self.spec.signature_head()
        sig = head + (self.block_size, self.num_blocks, str(self.dtype))
        if head[0] is self.spec:
            sig += (self.slots, self.launch_rows)
        return sig

    def blocks_for(self, seq_len: int) -> int:
        return max(1, math.ceil(seq_len / self.block_size))

    def pool_bytes(self) -> int:
        """Logical pool bytes (K + V across all layers), sharding-independent."""
        return sum(int(p.nbytes) for p in self._arrays())

    def per_chip_pool_bytes(self) -> int:
        """Resident KV bytes on one chip: pool_bytes()/tp under tp
        head-sharding, pool_bytes() unsharded (the ISSUE-12 residency gate)."""
        total = 0
        for p in self._arrays():
            shards = getattr(p, "addressable_shards", None)
            total += int(shards[0].data.nbytes) if shards else int(p.nbytes)
        return total

    def attach_prefix_cache(self, prefix):
        """Wire a PrefixCache into release/evict: refcount-zero indexed
        blocks park in its LRU tier instead of freeing, and _evict_lru
        drains that tier after finished-but-retained requests."""
        with self._lock:
            if self._prefix is not None and self._prefix is not prefix:
                raise ValueError("a prefix cache is already attached")
            self._prefix = prefix

    # ---------------------------------------------------------- observability
    def bind_metrics(self, registry, pool="kv"):
        """Register this pool's state on a MetricsRegistry
        (paddle_tpu/observability/metrics.py) as callback-read series —
        sampled at scrape time, no bookkeeping on the allocation hot path:

        * ``paddle_kv_pool_blocks{pool=...,state=live|free|evictable}``
        * ``paddle_kv_pool_live_utilization{pool=...}`` (admission signal)
        * ``paddle_kv_pool_evictions_total{pool=...}`` (monotonic)

        "live" counts still-decoding blocks (in_use minus evictable), so the
        three states partition the pool: live + free + evictable ==
        num_blocks, which the exposition-lint test checks off the scrape."""
        blocks = registry.gauge(
            "paddle_kv_pool_blocks",
            "KV page-pool blocks by state; live+free+evictable == pool size",
            labels=("pool", "state"))
        blocks.labels(pool, "live").set_function(
            lambda: self.blocks_in_use - self.evictable_blocks)
        blocks.labels(pool, "free").set_function(lambda: self.free_blocks)
        blocks.labels(pool, "evictable").set_function(
            lambda: self.evictable_blocks)
        registry.gauge(
            "paddle_kv_pool_size_blocks", "Total blocks in the KV page pool",
            labels=("pool",)).labels(pool).set_function(
                lambda: self.num_blocks)
        registry.gauge(
            "paddle_kv_pool_live_utilization",
            "Fraction of the pool held by still-decoding requests "
            "(the admission-control pressure signal)",
            labels=("pool",)).labels(pool).set_function(
                lambda: self.live_utilization)
        registry.counter(
            "paddle_kv_pool_evictions_total",
            "Finished-but-retained requests evicted LRU to cover new "
            "reservations", labels=("pool",)).labels(pool).set_function(
                lambda: self.evictions)
        registry.gauge(
            "paddle_kv_pool_per_chip_bytes",
            "KV pool bytes resident PER CHIP — 1/tp of the logical pool "
            "when the pool is head-sharded over the serving mesh's tp axis",
            labels=("pool",)).labels(pool).set_function(
                self.per_chip_pool_bytes)
        return self

    # ----------------------------------------------------------- allocation
    def reserve(self, request_id, max_seq_len: int, evict: bool = True,
                shared=None):
        """Allocate blocks covering max_seq_len for a new request; returns the
        block table as int32 [num_blocks_for(max_seq_len)]. When the free list
        runs dry and `evict`, finished-but-retained requests are evicted
        least-recently-used first, then the prefix cache's parked tier.

        ``shared`` is an optional list of (digest, block) pairs from a
        ``PrefixCache.lookup`` — the hint is revalidated HERE, under this
        lock (truncated at the first stale link), so a parked block evicted
        between lookup and reserve silently degrades the hit instead of
        aliasing someone else's pages. Validated blocks take a refcount and
        become the table's leading entries; the request's committed length
        starts at ``n_shared * block_size`` (those rows are already in the
        pool). Shared blocks never cover the final prompt token, so the
        first write a request issues lands past every shared block.

        Atomic: either the request ends up fully reserved, or the cache is
        byte-identical to before the call — in particular, nothing is evicted
        when eviction still could not cover the allocation, and a failed
        reservation re-parks any prefix blocks it had acquired."""
        with self._lock:
            if self._faults is not None:
                self._faults.check("kv.reserve")  # injected pool-dry faults
            if request_id in self._requests:
                raise ValueError(f"request {request_id!r} already reserved")
            n = self.blocks_for(max_seq_len)
            acquired: list[int] = []
            if shared and self._prefix is not None:
                # refcounts bump immediately so a done-holder released by the
                # eviction below can neither free nor re-park these blocks
                acquired = self._prefix._acquire(list(shared)[:n])
                for b in acquired:
                    self._block_refs[b] = self._block_refs.get(b, 0) + 1
            try:
                need_new = n - len(acquired)
                if self.allocator.available < need_new:
                    shortfall = need_new - self.allocator.available
                    if not evict or self._evictable_locked() < shortfall:
                        raise CacheOutOfBlocks(
                            f"need {need_new} blocks, "
                            f"{self.allocator.available} free + "
                            f"{self._evictable_locked() if evict else 0} "
                            f"evictable of {self.num_blocks}")
                    self._evict_lru(shortfall)
                fresh = self.allocator.allocate(need_new)  # CacheOutOfBlocks
            except BaseException:
                for b in acquired:     # undo: cache byte-identical to before
                    self._unref(b)
                raise
            for b in fresh:
                self._block_refs[b] = 1
            blocks = acquired + fresh
            self._requests[request_id] = _Request(
                blocks, len(acquired) * self.block_size, next(self._clock))
            return np.asarray(blocks, np.int32)

    def _evict_lru(self, need: int):
        with self._lock:
            done = sorted((r for r in self._requests.items() if r[1].done),
                          key=lambda kv: kv[1].touch)
            freed = 0
            for rid, req in done:
                if freed >= need:
                    break
                # blocks shared with live requests (or parked by the index)
                # don't come home on release — count the ACTUAL frees
                avail0 = self.allocator.available
                self.evictions += 1
                self.release(rid)
                got = self.allocator.available - avail0
                freed += got
                self.evicted_blocks += got
            if freed < need and self._prefix is not None:
                got = self._prefix._reclaim(need - freed)
                if got:
                    self.allocator.free(got)
                    self.evicted_blocks += len(got)

    def mark_done(self, request_id):
        """Request finished decoding; its pages stay readable (gather) but
        become evictable when the pool needs room."""
        with self._lock:
            self._requests[request_id].done = True

    def release(self, request_id):
        with self._lock:
            req = self._requests.pop(request_id)
            for b in req.blocks:
                self._unref(b)

    def _unref(self, block: int):
        """Drop one holder reference. At zero, an indexed block parks in
        the prefix tier (still matchable, reclaimable on demand); anything
        else goes back to the allocator. Callers already hold the lock —
        re-entering the RLock here keeps the method safe standalone."""
        with self._lock:
            r = self._block_refs[block] - 1
            if r > 0:
                self._block_refs[block] = r
                return
            del self._block_refs[block]
            if self._prefix is not None and self._prefix._park(block):
                return
            self.allocator.free([block])

    # ------------------------------------------------------------- metadata
    def block_table(self, request_id, pad_to=None):
        """int32 table of page ids; padded with page 0 (fetched-but-masked —
        the kernel requires valid page ids in dead slots)."""
        with self._lock:
            req = self._requests[request_id]
            req.touch = next(self._clock)
            tbl = list(req.blocks)
        if pad_to is not None:
            tbl += [0] * (int(pad_to) - len(tbl))
        return np.asarray(tbl, np.int32)

    def length(self, request_id) -> int:
        with self._lock:
            return self._requests[request_id].length

    def append_tokens(self, request_id, n: int) -> int:
        """Incremental append for chunked prefill / per-tick decode: advance
        the request's live length by `n` rows (monotonic, capacity-checked)
        and return the new length. set_length() remains the absolute-value
        form; this is the form a scheduler advancing per tick wants — it can
        never rewind another tick's progress."""
        if n < 0:
            raise ValueError(f"append_tokens: n must be >= 0, got {n}")
        with self._lock:
            req = self._requests[request_id]
            new = req.length + int(n)
            if new > len(req.blocks) * self.block_size:
                raise ValueError(
                    f"length {new} exceeds reserved capacity "
                    f"{len(req.blocks) * self.block_size}")
            req.length = new
            req.touch = next(self._clock)
            return new

    def set_length(self, request_id, n: int):
        with self._lock:
            req = self._requests[request_id]
            if n > len(req.blocks) * self.block_size:
                raise ValueError(
                    f"length {n} exceeds reserved capacity "
                    f"{len(req.blocks) * self.block_size}")
            req.length = int(n)

    @property
    def blocks_in_use(self) -> int:
        return self.allocator.in_use

    @property
    def free_blocks(self) -> int:
        return self.allocator.available

    @property
    def evictable_blocks(self) -> int:
        """Blocks reclaimable on demand: held ONLY by finished-but-retained
        requests (a done request's block shared with a live one cannot come
        home), plus the prefix cache's parked tier."""
        with self._lock:
            return self._evictable_locked()

    def _evictable_locked(self) -> int:
        done_held: set[int] = set()
        live_held: set[int] = set()
        for r in self._requests.values():
            (done_held if r.done else live_held).update(r.blocks)
        n = len(done_held - live_held)
        if self._prefix is not None:
            n += self._prefix.cached_blocks()
        return n

    @property
    def shared_block_count(self) -> int:
        """Blocks referenced by two or more request tables (the CoW wins)."""
        with self._lock:
            return sum(1 for v in self._block_refs.values() if v > 1)

    @property
    def utilization(self) -> float:
        return self.allocator.in_use / self.num_blocks

    @property
    def live_utilization(self) -> float:
        """Fraction of the pool held by still-DECODING requests — the
        admission-control pressure signal (done-but-retained blocks are
        reclaimable on demand, so they don't count as pressure)."""
        with self._lock:
            return (self.allocator.in_use - self.evictable_blocks) \
                / self.num_blocks

    # ----------------------------------------------------------- invariants
    def check_conservation(self) -> dict:
        """Ground-truth audit of the allocator + request + refcount
        bookkeeping; raises AssertionError on any violation, returns the
        recomputed stats.

        Invariants (the ones the continuous scheduler's churn leans on):
        * no block appears TWICE in one request's table, and every shared
          block's refcount equals a from-scratch recount of its holders
          (without a prefix cache this degenerates to the old rule: every
          block has exactly one owner);
        * held ∪ parked == the allocator's live set, held ∩ parked == ∅ —
          i.e. free ∪ live ∪ cached partitions the pool, with shared blocks
          counted ONCE (set semantics);
        * parked ⊆ indexed ⊆ live: the content index never names a freed
          block, and every parked block is matchable;
        * every request's length fits its reserved capacity;
        * ``live_utilization`` matches a from-scratch recomputation.
        Cheap enough to call after every op in the property tests and at the
        end of chaos storms."""
        with self._lock:
            holders: dict[int, int] = {}
            for rid, req in self._requests.items():
                seen_here: set[int] = set()
                for b in req.blocks:
                    assert 0 <= b < self.num_blocks, \
                        f"request {rid!r} holds out-of-pool block {b}"
                    assert b not in seen_here, \
                        f"block {b} appears twice in {rid!r}'s table"
                    seen_here.add(b)
                    holders[b] = holders.get(b, 0) + 1
                cap = len(req.blocks) * self.block_size
                assert req.length <= cap, \
                    (f"request {rid!r} length {req.length} exceeds "
                     f"capacity {cap}")
            if holders != self._block_refs:
                diff = {b: (holders.get(b), self._block_refs.get(b))
                        for b in set(holders) | set(self._block_refs)
                        if holders.get(b) != self._block_refs.get(b)}
                raise AssertionError(
                    f"refcounts diverge from recounted holders "
                    f"(block: (recount, refs)) = {diff}")
            held = set(holders)
            if self._prefix is not None:
                parked, indexed = self._prefix._tier_snapshot()
            else:
                parked, indexed = set(), set()
            assert not (held & parked), \
                f"blocks both held and parked: {held & parked}"
            live = self.allocator._live
            assert held | parked == live, \
                (f"held ∪ parked != allocator live set "
                 f"(held∪parked-not-live={(held | parked) - live}, "
                 f"live-not-accounted={live - held - parked})")
            assert parked <= indexed, \
                f"parked blocks missing from index: {parked - indexed}"
            assert indexed <= live, \
                f"index names freed blocks: {indexed - live}"
            free = set(self.allocator._free)
            assert len(free) == len(self.allocator._free), \
                "free list contains duplicates"
            assert not (free & live), f"blocks both free and live: {free & live}"
            assert len(free) + len(live) == self.num_blocks, \
                (f"free ({len(free)}) + live ({len(live)}) != "
                 f"pool size {self.num_blocks}")
            evictable = self._evictable_locked()
            expect_live_util = (len(live) - evictable) / self.num_blocks
            n_requests = len(self._requests)
            got = self.live_utilization
        assert abs(got - expect_live_util) < 1e-9, \
            f"live_utilization {got} != ground truth {expect_live_util}"
        return {"live": len(live), "free": len(free), "evictable": evictable,
                "cached": len(parked), "shared":
                    sum(1 for v in holders.values() if v > 1),
                "requests": n_requests, "live_utilization": got}

    # ------------------------------------------------------------ device I/O
    def commit(self, k_pages, v_pages):
        """Store the pools a compiled step returned (functional update).
        Locked: a concurrent gather() must see a matched (k, v) pair, never
        one old and one new pool list (thread-lint unguarded-write fix)."""
        if len(k_pages) != self.num_layers or len(v_pages) != self.num_layers:
            raise ValueError("pool list length != num_layers")
        with self._lock:
            self.k_pages = list(k_pages)
            self.v_pages = list(v_pages)

    def gather(self, request_id, layer: int):
        """Host-side contiguous [length, Hkv, D] (k, v) view of a request's
        cache — debug/audit path; the kernel never gathers. Locked end to
        end so a mid-gather commit() cannot mix pool generations."""
        with self._lock:
            req = self._requests[request_id]
            if not self.spec.is_uniform_kv():
                raise TypeError("gather reads K,V pages; this pool holds "
                                "other rows")
            n = self.blocks_for(max(req.length, 1))
            tbl = np.asarray(req.blocks[:n])

            def _dense(pages):
                # [n, BS, Hkv*D] -> [n*BS, Hkv, D]
                arr = np.asarray(pages)[tbl]
                return arr.reshape(-1, self.num_kv_heads,
                                   self.head_dim)[:req.length]

            return _dense(self.k_pages[layer]), _dense(self.v_pages[layer])

