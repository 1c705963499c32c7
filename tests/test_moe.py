"""MoE tests (VERDICT r1 item 4): routing vs a dense numpy reference,
load-balance loss, gradients, capacity drops, and expert-parallel a2a on the
8-device CPU mesh."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.incubate.distributed.models.moe import (
    GShardGate, MoELayer, NaiveGate, SwitchGate,
)

D, E, T = 8, 4, 32


class _ScaleExpert(nn.Layer):
    """Expert i: fixed known linear map (scale by i+1)."""

    def __init__(self, scale):
        super().__init__()
        self.fc = nn.Linear(D, D)
        self.fc.weight._value = jnp.eye(D, dtype=jnp.float32) * scale
        self.fc.bias._value = jnp.zeros(D, jnp.float32)

    def forward(self, x):
        return self.fc(x)


def _numpy_moe_reference(x, gate_w, k, capacity, scales):
    """Dense routing reference implementing the documented semantics in numpy."""
    logits = x @ gate_w
    probs = np.exp(logits - logits.max(1, keepdims=True))
    probs = probs / probs.sum(1, keepdims=True)
    T_, E_ = probs.shape
    masked = probs.copy()
    sel = []
    for _ in range(k):
        idx = masked.argmax(1)
        g = probs[np.arange(T_), idx]
        sel.append((idx, g))
        masked[np.arange(T_), idx] = 0.0
    if k > 1:
        denom = sum(g for _, g in sel) + 1e-9
        sel = [(i, g / denom) for i, g in sel]
    counts = np.zeros(E_, np.int64)
    out = np.zeros_like(x)
    contrib = []
    for idx, g in sel:
        for t in range(T_):
            e = idx[t]
            if counts[e] < capacity:
                contrib.append((t, e, g[t]))
            counts[e] += 1
        # reset per-round base: GShard counts earlier rounds first — emulate by
        # keeping the running counts across rounds (matches topk_capacity_routing)
    for t, e, g in contrib:
        out[t] += g * scales[e] * x[t]
    return out, probs


@pytest.mark.parametrize("gate_cls,k", [(SwitchGate, 1), (GShardGate, 2)])
def test_moe_routing_matches_dense_reference(gate_cls, k):
    paddle.seed(0)
    rs = np.random.RandomState(0)
    scales = [float(i + 1) for i in range(E)]
    experts = [_ScaleExpert(s) for s in scales]
    gate = gate_cls(D, E, capacity=(100.0, 100.0))  # ample capacity: nothing drops
    layer = MoELayer(D, experts, gate=gate)
    x_np = rs.randn(T, D).astype("float32")
    gate_w = np.asarray(gate.weight._value)
    out = layer(paddle.to_tensor(x_np))
    ref, probs = _numpy_moe_reference(x_np, gate_w, k, capacity=T, scales=scales)
    np.testing.assert_allclose(np.asarray(out._value), ref, rtol=1e-5, atol=1e-5)
    # load-balance loss formula: E * sum(mean_probs * mean_top1)
    top1 = np.zeros((T, E), np.float32)
    top1[np.arange(T), probs.argmax(1)] = 1
    expected_aux = E * np.sum(probs.mean(0) * top1.mean(0))
    np.testing.assert_allclose(float(layer.l_aux), expected_aux, rtol=1e-5)
    assert float(gate.get_loss()) == pytest.approx(expected_aux, rel=1e-5)


def test_moe_capacity_drops_tokens():
    paddle.seed(1)
    experts = [_ScaleExpert(1.0) for _ in range(E)]
    gate = SwitchGate(D, E, capacity=(0.25, 0.25))  # capacity 2 for T=32
    layer = MoELayer(D, experts, gate=gate)
    x = paddle.to_tensor(np.random.RandomState(1).randn(T, D).astype("float32"))
    out = np.asarray(layer(x)._value)
    dropped = np.sum(np.all(out == 0, axis=1))
    assert dropped > 0  # tokens beyond capacity contribute nothing


def test_moe_grads_flow():
    paddle.seed(2)
    experts = [nn.Sequential(nn.Linear(D, 2 * D), nn.GELU(), nn.Linear(2 * D, D))
               for _ in range(E)]
    layer = MoELayer(D, experts, gate="gshard")
    x = paddle.to_tensor(np.random.RandomState(2).randn(T, D).astype("float32"))
    out = layer(x)
    loss = out.sum() + layer.l_aux * 0.01
    loss.backward()
    assert layer.gate.weight.grad is not None
    n_with_grad = sum(
        1 for e in layer.experts for p in e.parameters()
        if p.grad is not None and float(jnp.abs(p.grad._value).sum()) > 0
    )
    assert n_with_grad > 0


def test_moe_under_jit_parity():
    paddle.seed(3)
    experts = [_ScaleExpert(float(i + 1)) for i in range(E)]
    layer = MoELayer(D, experts, gate="switch")
    x = paddle.to_tensor(np.random.RandomState(3).randn(T, D).astype("float32"))
    eager = np.asarray(layer(x)._value)
    jitted = paddle.jit.to_static(layer)
    out = np.asarray(jitted(x)._value)
    np.testing.assert_allclose(out, eager, rtol=1e-5, atol=1e-6)


def test_moe_expert_parallel_sharded():
    """8-device mesh with an 'ep' axis: the sharded MoE equals the unsharded."""
    import paddle_tpu.distributed as dist

    paddle.seed(4)
    experts = [_ScaleExpert(float(i + 1)) for i in range(8)]
    layer = MoELayer(D, experts, gate="gshard")
    x = paddle.to_tensor(np.random.RandomState(4).randn(T, D).astype("float32"))
    base = np.asarray(layer(x)._value)

    prev = dist.get_mesh()
    try:
        mesh = dist.ProcessMesh(np.arange(8).reshape(1, 8), ["dp", "ep"])
        dist.set_mesh(mesh)
        jitted = paddle.jit.to_static(layer)
        out = np.asarray(jitted(x)._value)
    finally:
        dist.set_mesh(prev)
    np.testing.assert_allclose(out, base, rtol=1e-5, atol=1e-6)


def test_global_scatter_gather_roundtrip():
    """a2a exchange on the 8-device mesh: gather(scatter(x)) == x, and scatter
    actually permutes rank-major blocks across devices."""
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    import paddle_tpu.distributed as dist
    from paddle_tpu.distributed.utils import global_gather, global_scatter

    world = 8
    devs = np.array(jax.devices()[:world])
    mesh = Mesh(devs, ("ep",))
    g = dist.collective.Group(ranks=list(range(world)), axis_name="ep")
    cap, d = 2, 4
    x = jnp.arange(world * world * cap * d, dtype=jnp.float32).reshape(
        world * world * cap, d)

    def roundtrip(v):
        t = paddle.Tensor(v)
        s = global_scatter(t, group=g)
        back = global_gather(s, group=g)
        return back._value

    out = jax.jit(shard_map(roundtrip, mesh=mesh, in_specs=P("ep"),
                            out_specs=P("ep")))(x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x))

    def scatter_only(v):
        return global_scatter(paddle.Tensor(v), group=g)._value

    out2 = jax.jit(shard_map(scatter_only, mesh=mesh, in_specs=P("ep"),
                             out_specs=P("ep")))(x)
    # rank-major block (i, j) must have moved to (j, i)
    blocks = np.asarray(out2).reshape(world, world, cap, d)
    orig = np.asarray(x).reshape(world, world, cap, d)
    np.testing.assert_allclose(blocks, np.swapaxes(orig, 0, 1))


# ---- round 5: index (gather/scatter) dispatch — the grouped-GEMM shape ----

def test_index_dispatch_matches_dense():
    """The O(k*T*d) index path must reproduce the dense one-hot einsum path
    bit-for-bit on routing decisions (same gate weights, same input)."""
    import numpy as np
    from paddle_tpu.incubate.distributed.models.moe import MoELayer

    D, E = 16, 4
    for gate_name in ("gshard", "switch", "naive"):
        paddle.seed(0)
        experts_a = [nn.Sequential(nn.Linear(D, 2 * D), nn.GELU(),
                                   nn.Linear(2 * D, D)) for _ in range(E)]
        dense = MoELayer(D, experts_a, gate=gate_name, dispatch_mode="dense")
        paddle.seed(0)
        experts_b = [nn.Sequential(nn.Linear(D, 2 * D), nn.GELU(),
                                   nn.Linear(2 * D, D)) for _ in range(E)]
        idx = MoELayer(D, experts_b, gate=gate_name, dispatch_mode="index")
        x = paddle.to_tensor(
            np.random.RandomState(0).randn(32, D).astype("float32"))
        ya = np.asarray(dense(x)._value)
        yb = np.asarray(idx(x)._value)
        np.testing.assert_allclose(yb, ya, rtol=1e-5, atol=1e-6,
                                   err_msg=gate_name)
        np.testing.assert_allclose(float(idx.l_aux), float(dense.l_aux),
                                   rtol=1e-6)


def test_index_dispatch_trains():
    import numpy as np
    from paddle_tpu.incubate.distributed.models.moe import MoELayer
    from paddle_tpu.jit.train import TrainStep

    D, E = 16, 4
    paddle.seed(0)

    class _M(nn.Layer):
        def __init__(self):
            super().__init__()
            self.moe = MoELayer(D, [nn.Sequential(nn.Linear(D, 2 * D),
                                                  nn.GELU(),
                                                  nn.Linear(2 * D, D))
                                    for _ in range(E)], gate="gshard")
            self.head = nn.Linear(D, 4)

        def forward(self, x):
            return self.head(self.moe(x))

    m = _M()
    opt = paddle.optimizer.AdamW(learning_rate=3e-3, parameters=m.parameters())
    lf = nn.CrossEntropyLoss()
    step = TrainStep(m, lambda o, y: lf(o, y) + m.moe.gate.get_loss(clear=False),
                     opt)
    rs = np.random.RandomState(0)
    x = paddle.to_tensor(rs.randn(32, D).astype("float32"))
    y = paddle.to_tensor(rs.randint(0, 4, 32).astype("int64"))
    losses = [float(step(x, y)) for _ in range(10)]
    assert losses[-1] < losses[0], losses
