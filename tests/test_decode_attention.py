"""Decode-attention kernel family + paged KV cache.

Covers the ISSUE-1 acceptance surface on CPU (Pallas interpret mode):
  * split-KV Pallas kernel vs XLA grouped-einsum parity — f32 and bf16, GQA
    ratios 1/4/8, prefix lengths including non-block-multiples, per-request
    lengths, S>1 (prefill-into-cache);
  * paged kernel (block-table indexed pages) parity + pool scatter semantics;
  * block allocator free-list reuse, OOM, and LRU eviction;
  * generate() token-parity between decode_kernel="pallas" and "xla";
  * generate_paged() mixed-length batches == per-request dense generate.
"""
import math
import re

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.ops.pallas import decode_attention as da

import jax.numpy as jnp


def _naive(q, k, v, lengths):
    """Loop-and-numpy reference (f32). k/v head-leading [B, Hkv, T, D]."""
    B, S, Hq, D = q.shape
    Hkv = k.shape[1]
    G = Hq // Hkv
    lengths = np.broadcast_to(np.asarray(lengths).reshape(-1), (B,))
    out = np.zeros(q.shape, np.float32)
    for b in range(B):
        for s in range(S):
            for h in range(Hq):
                n = h // G
                t = lengths[b] + s + 1          # causal horizon
                sc = (q[b, s, h].astype(np.float32)
                      @ k[b, n, :t].astype(np.float32).T) / np.sqrt(D)
                p = np.exp(sc - sc.max())
                p /= p.sum()
                out[b, s, h] = p @ v[b, n, :t].astype(np.float32)
    return out


def _rand(shape, dtype, rng):
    return jnp.asarray(rng.standard_normal(shape), dtype)


@pytest.mark.parametrize("gqa", [1, 4, 8])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_splitkv_parity(gqa, dtype):
    rng = np.random.default_rng(0)
    B, S, Hq, D, T = 2, 1, 8, 16, 64
    Hkv = Hq // gqa
    dt = jnp.dtype(dtype)
    q = _rand((B, S, Hq, D), dt, rng)
    k = _rand((B, Hkv, T, D), dt, rng)
    v = _rand((B, Hkv, T, D), dt, rng)
    length = 37                                  # not a block multiple
    ref = _naive(np.asarray(q, np.float32), np.asarray(k, np.float32),
                 np.asarray(v, np.float32), length)
    tol = 1e-5 if dtype == "float32" else 3e-2
    for kern in ("xla", "pallas"):
        got = np.asarray(da.decode_attention(q, k, v, length, kernel=kern),
                         np.float32)
        np.testing.assert_allclose(got, ref, atol=tol, rtol=tol,
                                   err_msg=f"{kern} gqa={gqa} {dtype}")


def test_splitkv_per_request_lengths_and_prefill():
    rng = np.random.default_rng(1)
    B, Hq, Hkv, D, T = 2, 4, 2, 16, 96
    q = _rand((B, 5, Hq, D), jnp.float32, rng)   # S>1: prefill-into-cache
    k = _rand((B, Hkv, T, D), jnp.float32, rng)
    v = _rand((B, Hkv, T, D), jnp.float32, rng)
    lengths = np.array([11, 60])                 # mixed, non-block-multiple
    ref = _naive(np.asarray(q), np.asarray(k), np.asarray(v), lengths)
    for kern in ("xla", "pallas"):
        got = np.asarray(da.decode_attention(q, k, v, jnp.asarray(lengths),
                                             kernel=kern))
        np.testing.assert_allclose(got, ref, atol=1e-5, rtol=1e-5,
                                   err_msg=kern)


def test_xla_path_has_no_repeated_kv():
    """The grouped-einsum XLA path must not materialize rep-expanded K/V:
    its jaxpr may not contain any array of the [B, T, Hq, D] shape."""
    import jax

    B, Hq, Hkv, D, T = 1, 8, 2, 16, 64
    rng = np.random.default_rng(2)
    q = _rand((B, 1, Hq, D), jnp.float32, rng)
    k = _rand((B, Hkv, T, D), jnp.float32, rng)
    v = _rand((B, Hkv, T, D), jnp.float32, rng)
    jaxpr = jax.make_jaxpr(
        lambda q, k, v: da.decode_attention_xla(q, k, v, 10))(q, k, v)
    expanded = (B, Hq, T, D)
    for eqn in jaxpr.jaxpr.eqns:
        for var in eqn.outvars:
            assert tuple(var.aval.shape) != expanded, eqn


def _dense_of(pages, tables, Hkv):
    """[P, BS, Hkv*D] pages through [B, NB] tables -> the head-leading
    [B, Hkv, NB*BS, D] cache `_naive` reads."""
    pages, tables = np.asarray(pages), np.asarray(tables)
    B, NB = tables.shape
    return pages[tables].reshape(B, NB * pages.shape[1], Hkv, -1).swapaxes(1, 2)


@pytest.mark.parametrize("Hq,Hkv,D", [
    (8, 2, 16),      # 32 lanes a row: the XLA gather under either kernel
    (8, 8, 16),      # 128 lanes: eight heads share the one lane group
])
def test_paged_parity_and_update(Hq, Hkv, D):
    rng = np.random.default_rng(3)
    B, S, BS, P, NB = 2, 1, 16, 12, 4
    assert da.paged_kernel_takes(Hq, Hkv, D) == (Hkv * D % 128 == 0)
    lengths = jnp.asarray([37, 20], jnp.int32)
    tables = jnp.asarray([[3, 7, 1, 9], [5, 2, 0, 0]], jnp.int32)
    k_pages = _rand((P, BS, Hkv * D), jnp.float32, rng)
    v_pages = _rand((P, BS, Hkv * D), jnp.float32, rng)
    q = _rand((B, S, Hq, D), jnp.float32, rng)
    ref = _naive(np.asarray(q), _dense_of(k_pages, tables, Hkv),
                 _dense_of(v_pages, tables, Hkv), np.asarray(lengths))
    for kern in ("xla", "pallas"):
        got = np.asarray(da.paged_decode_attention(q, k_pages, v_pages,
                                                   tables, lengths,
                                                   kernel=kern))
        np.testing.assert_allclose(got, ref, atol=1e-5, rtol=1e-5,
                                   err_msg=kern)

    # scatter: valid rows land at (table[pos//BS], pos%BS); invalid dropped
    k_new = _rand((B, 2, Hkv, D), jnp.float32, rng)
    v_new = _rand((B, 2, Hkv, D), jnp.float32, rng)
    valid = jnp.asarray([[True, True], [True, False]])
    pos = da.write_positions(lengths, 2, valid=valid, capacity=NB * BS)
    k2, _ = da.paged_cache_update(k_pages, v_pages, k_new, v_new, tables, pos)
    k2 = np.asarray(k2)
    rows = np.asarray(k_new).reshape(B, 2, Hkv * D)
    np.testing.assert_allclose(k2[1, 5], rows[0, 0])            # 37 -> p1s5
    np.testing.assert_allclose(k2[1, 6], rows[0, 1])
    np.testing.assert_allclose(k2[2, 4], rows[1, 0])            # 20 -> p2s4
    changed = (np.abs(k2 - np.asarray(k_pages)).max(axis=(1, 2)) > 0)
    assert changed.sum() == 2                   # pages 1 and 2 only


def _paged_batch(S, G, D, dtype, rng, Hkv=2):
    """Six slots over one pool of 128-row pages, table 3 wide (384 rows; the
    kernel folds 2 pages a trip, so the table is 1.5 blocks): lengths 0,
    k*BS - 1, k*BS and the full table, and two IDLE slots (table of page 0,
    no valid new row). Returns the arguments and, per slot, the pages it
    may read."""
    BS, NB, P = 128, 3, 14
    lengths = np.array([0, 2 * BS - 1, 2 * BS, NB * BS - S, 0, 77])
    new_rows = np.array([S, S, S, S, 0, 0])
    tables = np.zeros((6, NB), np.int32)
    pages = iter(rng.permutation(np.arange(1, P)))
    owned = []
    for b in range(6):
        n = -(-(lengths[b] + new_rows[b]) // BS) if new_rows[b] else 0
        tables[b, :n] = [next(pages) for _ in range(n)]
        owned.append(tables[b, :n].copy())
    dt = jnp.dtype(dtype)
    q = _rand((6, S, Hkv * G, D), dt, rng)
    k_pages = _rand((P, BS, Hkv * D), dt, rng)
    v_pages = _rand((P, BS, Hkv * D), dt, rng)
    return (q, k_pages, v_pages, jnp.asarray(tables),
            jnp.asarray(lengths, jnp.int32),
            jnp.asarray(new_rows, jnp.int32)), owned


@pytest.mark.parametrize("S,G,D,dtype,Hkv,budget", [
    (1, 1, 64, "bfloat16", 2, None),
    (1, 4, 128, "float32", 2, None),
    (1, 1, 64, "float32", 6, 2),      # 3 lane groups, room for 2: one a step
    (3, 4, 64, "bfloat16", 12, 4),    # 6 lane groups, room for 4: 3 a step
    (3, 1, 128, "float32", 2, None),
    (128, 1, 64, "bfloat16", 2, None),
    (128, 4, 128, "bfloat16", 1, None),
    (3, 2, 32, "float32", 4, None),   # four heads share the lane group
    (1, 1, 256, "float32", 2, 1),     # a head of two lane tiles, one a step
])
def test_paged_kernel_follows_lengths(S, G, D, dtype, Hkv, budget,
                                      monkeypatch):
    """The length-bounded paged kernel against `decode_attention_xla` through
    the gather path: decode, verify and prefill-chunk widths, grouped heads,
    head sizes on both sides of a lane tile (D < 128: the heads of one
    128-lane group are multiplied at once), both pool dtypes, lengths on
    every side of a page and a block edge, idle slots beside live ones, and
    a count of lane groups the VMEM budget does not divide (the step then
    DMAs a lane slice of each page, not the whole page)."""
    rng = np.random.default_rng(11)
    args, _ = _paged_batch(S, G, D, dtype, rng, Hkv)
    assert da.paged_kernel_takes(Hkv * G, Hkv, D)
    if budget is not None:
        R, isz = da._heads_per_group(D), jnp.dtype(dtype).itemsize
        fit = max(n for n in range(1, 64) if da.paged_tiling(
            n * R, 3, 128, D, S * G, isz)[0] == n * R)
        assert fit >= 8
        monkeypatch.setattr(da, "_VMEM_BUDGET",
                            budget * (da._VMEM_BUDGET // fit))
        hps, pps = da.paged_tiling(Hkv, 3, 128, D, S * G, isz)
        assert hps < Hkv and hps <= budget * R and Hkv % hps == 0
        assert hps % R == 0 and pps == 2
    *dense, new_rows = args
    got = np.asarray(da.paged_decode_attention(*dense, kernel="pallas",
                                               new_rows=new_rows), np.float32)
    ref = np.asarray(da.paged_decode_attention(*dense, kernel="xla"),
                     np.float32)
    live = np.asarray(new_rows) > 0
    tol = 2e-5 if dtype == "float32" else 3e-2
    np.testing.assert_allclose(got[live], ref[live], atol=tol, rtol=tol)
    assert np.isfinite(got).all()          # idle slots: finite, ignored


def test_paged_kernel_keeps_lane_neighbours_apart():
    """Two heads of 64 share a 128-lane group and each slot has its own
    length: a head's output is its own K and V alone. The kernel's output
    against a loop-and-numpy reference, and again with the OTHER head's
    lanes replaced by NaN-free but huge numbers in every page: head 0's
    rows must not move (the block-diagonal q leaves the neighbour's lanes
    out of the scores, and its share of p.v is never read)."""
    rng = np.random.default_rng(13)
    (q, k_pages, v_pages, tables, lengths, new_rows), _ = \
        _paged_batch(3, 2, 64, "float32", rng)
    got = np.asarray(da.paged_decode_attention(
        q, k_pages, v_pages, tables, lengths, new_rows=new_rows))
    ref = _naive(np.asarray(q), _dense_of(k_pages, tables, 2),
                 _dense_of(v_pages, tables, 2), np.asarray(lengths))
    live = np.asarray(new_rows) > 0
    np.testing.assert_allclose(got[live], ref[live], atol=2e-5, rtol=2e-5)
    loud_k = k_pages.at[:, :, 64:].multiply(50.0)
    loud_v = v_pages.at[:, :, 64:].add(1e4)
    loud = np.asarray(da.paged_decode_attention(
        q, loud_k, loud_v, tables, lengths, new_rows=new_rows))
    np.testing.assert_array_equal(loud[:, :, :2], got[:, :, :2])  # head 0
    assert np.abs(loud[live][:, :, 2:] - got[live][:, :, 2:]).min() > 1e3


def test_paged_kernel_reads_nothing_past_a_length():
    """Work follows length: with NaN in every page no live slot owns (page
    0, which the idle slots' tables and every table's tail name, among
    them) and in every owned page past the one a slot's last row is in, the
    kernel's output is finite and equal to the clean pool's. A kernel that
    fetched a dead table column, or walked an idle slot, would read NaN
    into a 0 x NaN product."""
    rng = np.random.default_rng(12)
    for S in (3,):
        (q, k_pages, v_pages, tables, lengths, new_rows), owned = \
            _paged_batch(S, 2, 64, "float32", rng)
        # the prefill-chunk shape of a valid mask: slot 1 has one valid row
        valid = np.arange(S)[None, :] < np.asarray(new_rows)[:, None]
        valid[1, 1:] = False
        new_rows = da.valid_new_rows(jnp.asarray(valid), S)
        assert list(np.asarray(new_rows)) == [S, 1, S, S, 0, 0]
        clean = np.ones(k_pages.shape[0], bool)
        for b, pages in enumerate(owned):
            rows = int(lengths[b]) + int(new_rows[b])
            clean[pages[:-(-rows // 128)]] = False
        poison = jnp.asarray(clean)[:, None, None]
        bad_k = jnp.where(poison, jnp.nan, k_pages)
        bad_v = jnp.where(poison, jnp.nan, v_pages)
        want = np.asarray(da.paged_decode_attention(
            q, k_pages, v_pages, tables, lengths, new_rows=new_rows))
        got = np.asarray(da.paged_decode_attention(
            q, bad_k, bad_v, tables, lengths, new_rows=new_rows))
        assert np.isfinite(got).all()
        np.testing.assert_array_equal(got, want)
        assert not np.asarray(got[4:]).any()    # idle slots: zeros


# Compiled for the chip, without the chip: the TPU's compiler is installed
# here and compiles for a described v5e. The interpreter proves the math;
# only this says Mosaic takes the kernel at the serving shapes. The topology
# is described inside the fixture (never at import: one worker at a time may
# load libtpu) and these tests live in this one file.
@pytest.fixture(scope="module")
def v5e_chip():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:     # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("B,S,Hq,Hkv,D,BS,P,NB", [
    (32, 1, 20, 20, 64, 32, 640, 32),      # gpt2-large decode_step
    (32, 128, 20, 20, 64, 32, 640, 32),    # gpt2-large prefill_chunk
    (32, 3, 10, 10, 64, 32, 640, 32),      # verify_step on a tp=2 shard
    (8, 64, 32, 8, 128, 16, 256, 64),      # llama GQA, D=128
])
def test_paged_kernel_compiles_for_the_v5e(v5e_chip, monkeypatch, B, S, Hq,
                                           Hkv, D, BS, P, NB):
    import jax

    monkeypatch.setattr(da, "_interpret", lambda: False)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)

    pool = arg((P, BS, Hkv * D), jnp.bfloat16)
    # the suite's "highest" matmul precision is for comparisons with numpy;
    # the chip runs the default, and Mosaic has no f32 pass over bf16 operands
    with jax.default_matmul_precision("default"):
        compiled = jax.jit(
            lambda q, kp, vp, tbl, ln, new: da._paged_decode_attention_impl(
                q, kp, vp, tbl, ln, new)).lower(
            arg((B, S, Hq, D), jnp.bfloat16), pool, pool,
            arg((B, NB), jnp.int32), arg((B,), jnp.int32),
            arg((B,), jnp.int32)).compile()
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') == 1


def _pool_sized(hlo, pool, dtype="bf16"):
    """(opcode, line) of every instruction of a compiled program whose
    result is an array of the pool's dtype and element count, in whatever
    shape (XLA flattens [P, BS, W] to [P*BS, W] around a scatter)."""
    out = []
    for line in hlo.splitlines():
        m = re.search(r"= (\w+)\[([0-9,]+)\]\S* ([\w-]+)\(", line)
        if (m and m.group(1) == dtype and math.prod(
                int(n) for n in m.group(2).split(",")) == math.prod(pool)):
            out.append((m.group(3), line.strip()))
    return out


@pytest.mark.parametrize("B,S,Hq,Hkv,D,BS,P,NB", [
    (32, 1, 20, 20, 64, 32, 640, 32),      # gpt2-large decode_step
    (32, 128, 20, 20, 64, 32, 640, 32),    # gpt2-large prefill_chunk
    (32, 3, 10, 10, 64, 32, 640, 32),      # verify_step on a tp=2 shard
    (8, 64, 32, 8, 128, 16, 2048, 64),     # llama GQA, D=128
])
def test_step_programs_hold_no_copy_of_the_pool(v5e_chip, monkeypatch, B, S,
                                                Hq, Hkv, D, BS, P, NB):
    """ONE layout from the writer through the loop's carry to the kernel:
    the row writer and the paged kernel of one layer, inside a `lax.scan`
    that carries donated pools (the shape of `generate_paged` and of the
    decode tick), compiled for the described v5e. Nothing of the pool's
    size is left in the program but the pools themselves, views of them
    (bitcast), the scatter's in-place update and the fusion XLA wraps it in:
    no copy, no transpose, no prefetch of a pool (a pool small enough for
    the compiler to park in VMEM would be: the LLaMA case holds 2,048 pages
    for that), and one Mosaic call."""
    import jax

    monkeypatch.setattr(da, "_interpret", lambda: False)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)

    def program(kp, vp, q, k_new, v_new, tbl, ln):
        def step(carry, _):
            kp, vp, ln = carry
            kp, vp = da.paged_cache_update(kp, vp, k_new, v_new, tbl,
                                           da.write_positions(ln, S))
            out = da._paged_decode_attention_impl(
                q, kp, vp, tbl, ln, jnp.full((B,), S, jnp.int32))
            return (kp, vp, ln + S), out[:, -1]

        (kp, vp, _), outs = jax.lax.scan(step, (kp, vp, ln), None, length=2)
        return kp, vp, outs

    pool = (P, BS, Hkv * D)
    rows = arg((B, S, Hkv, D), jnp.bfloat16)
    with jax.default_matmul_precision("default"):
        hlo = jax.jit(program, donate_argnums=(0, 1)).lower(
            arg(pool, jnp.bfloat16), arg(pool, jnp.bfloat16),
            arg((B, S, Hq, D), jnp.bfloat16), rows, rows,
            arg((B, NB), jnp.int32), arg((B,), jnp.int32)).compile().as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 1
    found = _pool_sized(hlo, pool)
    assert {"parameter", "scatter"} <= {op for op, _ in found}
    stray = [line for op, line in found if op not in (
        "parameter", "get-tuple-element", "bitcast", "scatter", "fusion")]
    assert not stray, stray
    # the fusions are the scatters' own: in place, nothing else inside
    assert all("kind=kCustom" in line for op, line in found if op == "fusion")


def test_no_x64_leak_into_pallas_calls():
    """paddle_tpu runs with jax_enable_x64 on; any f64/i64 operand reaching a
    pallas_call breaks Mosaic on the real chip (no f64 vector ops). Trace both
    kernels with HOSTILE dtypes (f64 q, i64 lengths/tables) and assert the
    wrappers normalized everything before the kernel boundary."""
    import jax

    def walk(jaxpr, out):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                out.append(eqn)
            for val in eqn.params.values():
                for v in (val if isinstance(val, (list, tuple)) else [val]):
                    inner = getattr(v, "jaxpr", None)
                    if inner is not None:
                        walk(inner if hasattr(inner, "eqns") else inner.jaxpr,
                             out)
        return out

    B, S, Hq, Hkv, D, T = 2, 1, 8, 2, 16, 64
    q = jnp.zeros((B, S, Hq, D), jnp.float64)
    k = jnp.zeros((B, Hkv, T, D), jnp.float32)
    ln = jnp.zeros((B,), jnp.int64)
    tables = jnp.zeros((B, 4), jnp.int64)
    kp = jnp.zeros((8, 16, Hq * D), jnp.float32)   # 8 kv heads: 128 lanes
    for jx in (
        jax.make_jaxpr(lambda q, k, ln: da.decode_attention(q, k, k, ln))(
            q, k, ln),
        jax.make_jaxpr(lambda q, kp, t, ln: da.paged_decode_attention(
            q, kp, kp, t, ln))(q, kp, tables, ln),
    ):
        eqns = walk(jx.jaxpr, [])
        assert eqns, "pallas_call not found in trace"
        bad = [str(v.aval) for e in eqns for v in e.invars
               if getattr(v.aval, "dtype", None) in (jnp.float64, jnp.int64)]
        assert not bad, bad


# ------------------------------------------------------------- allocator/pool
def test_block_allocator_reuse_and_oom():
    from paddle_tpu.inference.kv_cache import BlockAllocator, CacheOutOfBlocks

    a = BlockAllocator(4)
    first = a.allocate(2)
    assert a.available == 2 and a.in_use == 2
    a.free(first)
    with pytest.raises(ValueError):
        a.free(first)                           # double free
    again = a.allocate(2)
    assert set(again) == set(first)             # free-list reuse
    a.allocate(2)
    with pytest.raises(CacheOutOfBlocks):
        a.allocate(1)


def test_paged_cache_reserve_release_evict():
    from paddle_tpu.inference.kv_cache import CacheOutOfBlocks, PagedKVCache

    c = PagedKVCache(num_layers=1, num_kv_heads=2, head_dim=8, block_size=4,
                     num_blocks=8, dtype="float32")
    t1 = c.reserve("r1", 10)                    # 3 blocks
    t2 = c.reserve("r2", 16)                    # 4 blocks
    assert len(t1) == 3 and len(t2) == 4 and c.blocks_in_use == 7
    assert len(c.block_table("r1", pad_to=5)) == 5
    with pytest.raises(CacheOutOfBlocks):
        c.reserve("r3", 8)                      # needs 2, only 1 free, no one done
    c.mark_done("r1")
    c.reserve("r3", 8)                          # evicts r1 (LRU done)
    assert c.blocks_in_use == 6
    with pytest.raises(KeyError):
        c.block_table("r1")                     # evicted
    c.release("r2")
    c.release("r3")
    assert c.blocks_in_use == 0 and c.utilization == 0.0
    with pytest.raises(KeyError):
        c.set_length("nope", 1)


def test_paged_cache_length_capacity_guard():
    from paddle_tpu.inference.kv_cache import PagedKVCache

    c = PagedKVCache(1, 2, 8, block_size=4, num_blocks=4, dtype="float32")
    c.reserve("r", 6)                           # 2 blocks = capacity 8
    c.set_length("r", 8)
    with pytest.raises(ValueError):
        c.set_length("r", 9)


# ------------------------------------------------------- generate() parity
def _gpt(**over):
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    cfg = dict(vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
               num_kv_heads=2, max_position=64, dropout=0.0)
    cfg.update(over)
    with paddle.utils.unique_name.guard():
        paddle.seed(7)
        m = GPTForCausalLM(GPTConfig(**cfg))
    m.eval()
    return m


def _greedy_reference(model, ids, n):
    """Cache-free greedy decoding by full eager forwards. The buffer is
    right-padded to the final length up front: under causal attention
    position t sees only tokens <= t, so the padding changes nothing and
    every forward has ONE shape (growing the sequence recompiled every eager
    op six times — 12 s of this file's tier-1 wall)."""
    import jax.numpy as jnp

    ids = np.asarray(ids)
    plen = ids.shape[1]
    buf = np.zeros((ids.shape[0], plen + n), ids.dtype)
    buf[:, :plen] = ids
    with paddle.no_grad():
        for t in range(plen, plen + n):
            logits = model(paddle.to_tensor(buf))
            buf[:, t] = np.asarray(jnp.argmax(logits._value[:, t - 1], axis=-1))
    return buf


def test_generate_token_parity_pallas_vs_xla():
    m = _gpt()
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, 128, (2, 5)).astype("int64")
    want = _greedy_reference(m, prompt, 6)
    for kern in ("xla", "pallas"):
        got = np.asarray(m.generate(paddle.to_tensor(prompt),
                                    max_new_tokens=6, dtype=None,
                                    decode_kernel=kern)._value)
        np.testing.assert_array_equal(got, want, err_msg=kern)


def test_llama_generate_token_parity():
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny

    with paddle.utils.unique_name.guard():
        paddle.seed(7)
        m = LlamaForCausalLM(llama_tiny())
    m.eval()
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, 512, (2, 5)).astype("int64")
    want = _greedy_reference(m, prompt, 6)
    for kern in ("xla", "pallas"):
        got = np.asarray(m.generate(paddle.to_tensor(prompt),
                                    max_new_tokens=6, dtype=None,
                                    decode_kernel=kern)._value)
        np.testing.assert_array_equal(got, want, err_msg=kern)


@pytest.mark.slow   # ~10s: ISSUE-17 wall paydown — ragged-batch paged parity
# stays anchored tier-1 by test_generate_batching_predictor_serves_mixed_lengths
# (same paged API through the batcher) + the continuous-serving dense references
def test_generate_paged_mixed_lengths_match_dense():
    from paddle_tpu.inference.kv_cache import PagedKVCache

    m = _gpt()
    rng = np.random.default_rng(0)
    NEW = 5
    prompts = [rng.integers(0, 128, n).astype("int64") for n in (5, 9, 3)]
    refs = [np.asarray(m.generate(paddle.to_tensor(p[None]),
                                  max_new_tokens=NEW, dtype=None,
                                  decode_kernel="xla")._value)[0]
            for p in prompts]
    cache = PagedKVCache(2, 2, 16, block_size=8, num_blocks=24,
                         dtype="float32")
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
    for kern in ("xla", "pallas"):
        toks = np.asarray(m.generate_paged(batch, plens, cache, tbl,
                                           max_new_tokens=NEW,
                                           decode_kernel=kern)._value)
        for i, (p, ref) in enumerate(zip(prompts, refs)):
            np.testing.assert_array_equal(toks[i], ref[len(p):],
                                          err_msg=f"{kern} req {i}")


def test_generate_paged_learned_positions():
    """GPT-2-style config (no rope): the paged path gathers POSITION
    embeddings per request ([B, S] clipped ids), a distinct codepath from
    rope's absolute-frequency rotation."""
    from paddle_tpu.inference.kv_cache import PagedKVCache

    m = _gpt(use_rope=False, use_rms_norm=False, use_swiglu=False,
             num_kv_heads=4)
    rng = np.random.default_rng(1)
    NEW = 2
    prompts = [rng.integers(0, 128, n).astype("int64") for n in (3, 5)]
    refs = [np.asarray(m.generate(paddle.to_tensor(p[None]),
                                  max_new_tokens=NEW, dtype=None,
                                  decode_kernel="xla")._value)[0]
            for p in prompts]
    cache = PagedKVCache(2, 4, 16, block_size=8, num_blocks=8,
                         dtype="float32")
    plens = np.asarray([3, 5])
    batch = np.zeros((2, 5), np.int64)
    for i, p in enumerate(prompts):
        batch[i, :len(p)] = p
    for i in range(2):
        cache.reserve(i, int(plens[i]) + NEW)
    tbl = np.stack([cache.block_table(i, pad_to=1) for i in range(2)])
    toks = np.asarray(m.generate_paged(batch, plens, cache, tbl,
                                       max_new_tokens=NEW,
                                       decode_kernel="pallas")._value)
    for i, (p, ref) in enumerate(zip(prompts, refs)):
        np.testing.assert_array_equal(toks[i], ref[len(p):], err_msg=str(i))


def test_generate_batching_predictor_serves_mixed_lengths():
    import threading

    from paddle_tpu.inference.serving import GenerateBatchingPredictor

    m = _gpt()
    rng = np.random.default_rng(0)
    NEW = 4
    prompts = [rng.integers(0, 128, n).astype("int64") for n in (4, 7)]
    refs = [np.asarray(m.generate(paddle.to_tensor(p[None]),
                                  max_new_tokens=NEW, dtype=None,
                                  decode_kernel="xla")._value)[0]
            for p in prompts]
    gp = GenerateBatchingPredictor(m, max_batch_size=4, max_delay_ms=30,
                                   max_new_tokens=NEW, decode_kernel="pallas",
                                   block_size=8, num_blocks=16)
    try:
        results = {}

        def call(i, p):
            results[i] = gp.infer(p, timeout=300)

        threads = [threading.Thread(target=call, args=(i, p))
                   for i, p in enumerate(prompts)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for i, ref in enumerate(refs):
            np.testing.assert_array_equal(results[i], ref, err_msg=f"req {i}")
        assert gp.kv_cache.blocks_in_use == 0    # pool drained after serving
    finally:
        gp.close()
