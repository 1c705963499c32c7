"""ProcessMesh + placements: the spine of the distributed design.

Reference parity: `paddle.distributed.ProcessMesh` + `Shard/Replicate/Partial`
(python/paddle/distributed/auto_parallel/api.py, placement_types in
paddle/phi/core/distributed/auto_parallel/placement_types.h). TPU-native: a ProcessMesh
wraps a `jax.sharding.Mesh`; placements translate to `jax.sharding.PartitionSpec` and
GSPMD inserts the collectives (SURVEY.md §5: "delete the NCCL layer concept").
"""
from __future__ import annotations

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec


class Placement:
    def is_shard(self, dim=None):
        return False

    def is_replicate(self):
        return False

    def is_partial(self):
        return False


class Shard(Placement):
    def __init__(self, dim):
        self.dim = dim

    def is_shard(self, dim=None):
        return True if dim is None else dim == self.dim

    def get_dim(self):
        return self.dim

    def __repr__(self):
        return f"Shard(dim={self.dim})"

    def __eq__(self, other):
        return isinstance(other, Shard) and other.dim == self.dim

    def __hash__(self):
        return hash(("shard", self.dim))


class Replicate(Placement):
    def is_replicate(self):
        return True

    def __repr__(self):
        return "Replicate()"

    def __eq__(self, other):
        return isinstance(other, Replicate)

    def __hash__(self):
        return hash("replicate")


class Partial(Placement):
    """Pending-reduction placement. GSPMD has no user-visible partial state; we model it
    as replicate + a recorded reduce op so `reshard` to Replicate emits the reduction
    (mirrors reference p_to_r reshard function)."""

    def __init__(self, reduce_type="sum"):
        self.reduce_type = reduce_type

    def is_partial(self):
        return True

    def __repr__(self):
        return f"Partial({self.reduce_type})"

    def __eq__(self, other):
        return isinstance(other, Partial) and other.reduce_type == self.reduce_type

    def __hash__(self):
        return hash(("partial", self.reduce_type))


class ProcessMesh:
    """Reference: auto_parallel ProcessMesh(mesh, dim_names). Backed by jax Mesh over
    the available devices (or a subset)."""

    def __init__(self, mesh, dim_names=None, process_ids=None):
        arr = np.asarray(mesh)
        if dim_names is None:
            dim_names = [f"d{i}" for i in range(arr.ndim)]
        self._shape = list(arr.shape)
        self._dim_names = list(dim_names)
        self._process_ids = arr.reshape(-1).tolist()
        devices = jax.devices()
        n = arr.size
        if n > len(devices):
            raise ValueError(
                f"mesh needs {n} devices but only {len(devices)} available; for CPU "
                f"testing set XLA_FLAGS=--xla_force_host_platform_device_count={n}"
            )
        dev_arr = np.asarray([devices[i] for i in arr.reshape(-1)]).reshape(arr.shape)
        self._jax_mesh = Mesh(dev_arr, axis_names=tuple(self._dim_names))

    @property
    def shape(self):
        return list(self._shape)

    @property
    def dim_names(self):
        return list(self._dim_names)

    @property
    def process_ids(self):
        return list(self._process_ids)

    @property
    def mesh(self):
        return np.asarray(self._process_ids).reshape(self._shape)

    @property
    def jax_mesh(self) -> Mesh:
        return self._jax_mesh

    @property
    def ndim(self):
        return len(self._shape)

    def get_dim_size(self, name):
        return self._shape[self._dim_names.index(name)]

    def get_rank_by_dim_and_process_id(self, dim, pid):
        idx = self._process_ids.index(pid)
        coords = np.unravel_index(idx, self._shape)
        return int(coords[self._dim_names.index(dim) if isinstance(dim, str) else dim])

    def __eq__(self, other):
        return (
            isinstance(other, ProcessMesh)
            and other._shape == self._shape
            and other._dim_names == self._dim_names
            and other._process_ids == self._process_ids
        )

    def __hash__(self):
        return hash((tuple(self._shape), tuple(self._dim_names), tuple(self._process_ids)))

    def __repr__(self):
        return f"ProcessMesh(shape={self._shape}, dim_names={self._dim_names})"


_global_mesh: ProcessMesh | None = None


def set_mesh(mesh: ProcessMesh):
    global _global_mesh
    _global_mesh = mesh
    return mesh


def get_mesh() -> ProcessMesh | None:
    return _global_mesh


def auto_mesh(*axis_sizes, dim_names=None) -> ProcessMesh:
    """Build a mesh over all devices with the given axis sizes (row-major)."""
    n = int(np.prod(axis_sizes))
    ids = np.arange(n).reshape(axis_sizes)
    return ProcessMesh(ids, dim_names)


def placements_to_spec(placements, ndim) -> PartitionSpec:
    """Translate paddle placements (index = mesh dim) to a PartitionSpec (index = tensor
    dim). Multiple mesh axes sharding the same tensor dim become a tuple entry."""
    entries: list = [None] * ndim
    return _placements_to_spec_entries(placements, entries)


def _placements_to_spec_entries(placements, entries):
    mesh = get_mesh()
    for mesh_dim, pl in enumerate(placements):
        if isinstance(pl, Shard):
            tdim = pl.get_dim()
            name = None
            if mesh is not None and mesh_dim < len(mesh.dim_names):
                name = mesh.dim_names[mesh_dim]
            if entries[tdim] is None:
                entries[tdim] = name
            elif isinstance(entries[tdim], tuple):
                entries[tdim] = entries[tdim] + (name,)
            else:
                entries[tdim] = (entries[tdim], name)
    return PartitionSpec(*entries)


def spec_for(mesh: ProcessMesh, placements, ndim) -> PartitionSpec:
    entries: list = [None] * ndim
    for mesh_dim, pl in enumerate(placements):
        if isinstance(pl, Shard):
            tdim = pl.get_dim()
            name = mesh.dim_names[mesh_dim]
            if entries[tdim] is None:
                entries[tdim] = name
            elif isinstance(entries[tdim], tuple):
                entries[tdim] = entries[tdim] + (name,)
            else:
                entries[tdim] = (entries[tdim], name)
    return PartitionSpec(*entries)


def sharding_for(mesh: ProcessMesh, placements, ndim) -> NamedSharding:
    return NamedSharding(mesh.jax_mesh, spec_for(mesh, placements, ndim))


# --------------------------------------------------------------- compute mesh
# Pipeline stage programs trace model code on a SUB-mesh of the global mesh;
# sharding constraints written against the global mesh would reference devices
# outside the stage. Stage executables set this override while tracing.
_compute_mesh_override = None
_NO_MESH = object()  # explicit "no constraints" override (single-device stage)


class _ComputeMeshCtx:
    def __init__(self, jax_mesh):
        self._mesh = jax_mesh if jax_mesh is not None else _NO_MESH
        self._prev = None

    def __enter__(self):
        global _compute_mesh_override
        self._prev = _compute_mesh_override
        _compute_mesh_override = self._mesh
        return self._mesh

    def __exit__(self, *exc):
        global _compute_mesh_override
        _compute_mesh_override = self._prev
        return False


def compute_mesh(jax_mesh) -> _ComputeMeshCtx:
    """Context manager: route model-code sharding constraints to `jax_mesh`
    (None = suppress constraints entirely, for single-device stage programs)."""
    return _ComputeMeshCtx(jax_mesh)


def current_jax_mesh():
    """The jax Mesh that sharding constraints in model code should target: the
    stage-program override when active, else the global ProcessMesh's mesh."""
    if _compute_mesh_override is _NO_MESH:
        return None
    if _compute_mesh_override is not None:
        return _compute_mesh_override
    m = get_mesh()
    return m.jax_mesh if m is not None else None


def constrain(val, entries, force=False):
    """with_sharding_constraint(val, entries) against the current compute mesh,
    dropping axis names the mesh doesn't carry and axes that don't divide.
    entries: list of axis-name / tuple / None per tensor dim. No-op outside a
    trace or without a mesh. With force=True an all-replicated result still
    emits the constraint (used to demand an all-gather)."""
    import jax as _jax

    jm = current_jax_mesh()
    if jm is None or not isinstance(val, _jax.core.Tracer):
        return val
    sizes = dict(zip(jm.axis_names, jm.devices.shape))

    def keep(names, dim_size):
        if names is None:
            return None
        tup = names if isinstance(names, tuple) else (names,)
        tup = tuple(n for n in tup if sizes.get(n, 1) > 1)
        if not tup:
            return None
        total = 1
        for n in tup:
            total *= sizes[n]
        if dim_size % total != 0:
            return None
        return tup if len(tup) > 1 else tup[0]

    kept = [keep(e, val.shape[i]) for i, e in enumerate(entries)]
    if all(k is None for k in kept) and not force:
        return val
    return _jax.lax.with_sharding_constraint(
        val, NamedSharding(jm, PartitionSpec(*kept)))


def mesh_axis_size(name, jax_mesh=None) -> int:
    """Size of a named axis on the given (default: current compute) jax mesh;
    1 when there is no mesh or the axis is absent — callers can gate sharded
    paths on `mesh_axis_size("tp") > 1` without null checks."""
    jm = jax_mesh if jax_mesh is not None else current_jax_mesh()
    if jm is None or name not in jm.axis_names:
        return 1
    return int(dict(zip(jm.axis_names, jm.devices.shape))[name])


def per_shard(local_fn, args, dims, out_dims, head_dim=1):
    """Run `local_fn(*args)` once per (batch, head) shard of the current
    compute mesh — for a call GSPMD cannot partition (a Mosaic kernel: "wrap
    the call in a shard_map") whose math is independent per batch row and per
    head. `dims` gives one string per argument, one letter per leading
    dimension: "b" batch, "h" heads, "H" heads side by side in one
    dimension, `head_dim` numbers each (it counts as extent / head_dim
    heads), "." anything else ("b.h." for [B, S, H, D], "..H" for a
    [pages, rows, heads x head_dim] pool, "" for a table every shard reads
    whole); `out_dims` the same for the single output.

    Batch shards over `dp` and heads over the tensor axis (`mp` | `tp`), each
    only where the axis divides every such dimension. A head dimension of 1
    broadcasts and stays replicated; GQA head counts must nest, so that a
    block of q heads meets its own kv heads. Every other axis (sep, pp, ...)
    sees the operands replicated. No mesh, one device, or already inside a
    shard_map (ring attention made everything local): a direct call."""
    jm = current_jax_mesh()
    if (jm is None or jm.size == 1
            or jax.sharding.get_abstract_mesh().manual_axes):
        return local_fn(*args)
    sizes = dict(zip(jm.axis_names, jm.devices.shape))

    def heads_of(a, i, c):
        return a.shape[i] // head_dim if c == "H" else a.shape[i]

    def extents(letter):
        return [heads_of(a, i, c) for a, d in zip(args, dims)
                for i, c in enumerate(d) if c.lower() == letter]

    def axis_for(names, ns):
        return next((ax for ax in names if sizes.get(ax, 1) > 1
                     and all(n % sizes[ax] == 0 for n in ns)), None)

    nh = [n for n in extents("h") if n > 1]
    batch = axis_for(("dp",), extents("b")) if extents("b") else None
    heads = (axis_for(("mp", "tp"), nh)
             if nh and all(n % min(nh) == 0 for n in nh) else None)

    def spec(d, a=None):
        return PartitionSpec(*[
            batch if c == "b"
            else heads if c in "hH" and (a is None or heads_of(a, i, c) > 1)
            else None for i, c in enumerate(d)])

    return jax.shard_map(
        local_fn, mesh=jm,
        in_specs=tuple(spec(d, a) for a, d in zip(args, dims)),
        out_specs=spec(out_dims), check_vma=False)(*args)


# ------------------------------------------------------------ serving layouts
class SpecLayout:
    """Canonical partition entries for the ("dp","tp") serving mesh (SNIPPETS
    SpecLayout pattern): tp rides the qkv/ffn/embedding tensor axes, the paged
    KV pool head-shards on its last axis, and everything slot-shaped stays
    replicated — dp carries no in-program sharding because data parallelism
    lives at the scheduler-replica level (`ReplicaFleet`)."""

    def __init__(self, dp_axis="dp", tp_axis="tp"):
        self.dp_axis = dp_axis
        self.tp_axis = tp_axis

    def kv_pool(self):
        """[pages, block, Hkv x head_dim] — a row's heads are lane groups
        of its last axis, so head-sharding is a last-dim shard."""
        return (None, None, self.tp_axis)

    def heads(self, ndim=4, head_dim=2):
        """Head-major activations, e.g. q [B, S, Hq, D]."""
        entries = [None] * ndim
        entries[head_dim] = self.tp_axis
        return tuple(entries)

    def logits(self):
        """[slots, vocab] logits before sampling: vocab-sharded over tp (the
        tied lm_head is a VocabParallelEmbedding row shard)."""
        return (None, self.tp_axis)

    def replicated(self, ndim):
        return (None,) * ndim

    # ------------------------------------------------- declared contracts
    # The two halves of the static comms gate (analysis/comms.py, ISSUE-20)
    # live HERE because this class is the layout's single declaration
    # point: the lint compares what XLA actually compiled against what
    # this file says, so drift between them is a finding, not a shrug.

    def step_contract(self) -> dict:
        """The declared input-layout contract of the serving step programs:
        glob over flattened argument labels (``state.<param>``,
        ``k_pages.<layer>``, ...) -> partition entries. Only labels every
        step path carries appear — a glob that matches nothing in a
        compiled program is itself ``layout-contract-drift``."""
        tp = self.tp_axis
        return {
            # Megatron column shards: qkv + fused gate_up split the output
            # dim; their row-parallel partners split the input dim and own
            # the partial-sum all-reduce.
            "state.*qkv_proj.weight": (None, tp),
            "state.*gate_up.weight": (None, tp),
            "state.*out_proj.weight": (tp, None),
            "state.*down.weight": (tp, None),
            # VocabParallelEmbedding row shard — doubles as the tied
            # lm_head, which is what makes the logits vocab-sharded.
            "state.embed_tokens.weight": (tp, None),
            "state.*ln*.weight": (),
            # the paged pool head-shards on its last axis (kv_pool())
            "k_pages*": (None, None, tp),
            "v_pages*": (None, None, tp),
            # host-side knobs stay replicated: sampler params, block
            # tables and the PRNG key are scheduler state, never sharded
            "tables": (),
            "temperatures": (),
            "top_ks": (),
            "rng_key": (),
        }

    def expected_collectives(self) -> dict:
        """Collective kinds the declared layout transitions explain, with
        their reasons — the ``implicit-reshard`` whitelist. Anything the
        compiled step programs emit beyond these kinds is cross-chip
        traffic nobody declared."""
        return {
            "all-reduce":
                "row-parallel / vocab-parallel partial sums (out_proj, "
                "down, embedding lookup) and vocab-sharded sampling "
                "reductions",
            "all-gather":
                "the sampled-logits gather: vocab-sharded [slots, V] "
                "logits reduced per shard, gathered to pick the token "
                "(the split-KV decode path's one documented exchange)",
        }


def serving_mesh(dp=1, tp=1, *, set_global=True) -> ProcessMesh:
    """Build (and by default install as the global mesh) the ("dp","tp")
    serving mesh over the first dp*tp devices. tp shards the step programs'
    weights and KV pool; dp is the replica-fleet axis."""
    ids = np.arange(dp * tp).reshape(dp, tp)
    m = ProcessMesh(ids, ["dp", "tp"])
    if set_global:
        set_mesh(m)
    return m
