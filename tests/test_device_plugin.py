"""Device-plugin ABI (SURVEY §9 decision): PJRT plays the reference's
custom-device C ABI role (paddle/phi/backends/device_ext.h:26) and the
custom-engine whole-graph hook (custom_engine_ext.h). These tests pin what the
framework relies on from it: devices come through one client interface, and a
saved program is not tied to the backend that produced it."""
import jax


def test_current_backend_is_pjrt_served():
    """Whatever platform serves this test session (cpu here, libtpu on the
    chip), devices come through the same PJRT client interface — the single
    ABI the framework targets."""
    devs = jax.devices()
    assert devs, "no devices from the PJRT client"
    d = devs[0]
    for attr in ("platform", "device_kind", "process_index"):
        assert hasattr(d, attr)


def test_stablehlo_artifact_is_plugin_agnostic(tmp_path):
    """The jit.save artifact compiles via ANY PJRT backend: re-load and
    execute on the CPU backend regardless of what produced it (the
    custom-engine whole-graph-compile role: the plugin owns compilation of
    the full StableHLO module)."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import nn

    paddle.seed(0)
    m = nn.Sequential(nn.Linear(4, 8), nn.GELU(), nn.Linear(8, 2))
    m.eval()
    p = str(tmp_path / "m")
    paddle.jit.save(m, p, input_spec=[paddle.static.InputSpec([None, 4])])
    loaded = paddle.jit.load(p)
    x = np.random.RandomState(0).randn(3, 4).astype("float32")
    np.testing.assert_allclose(
        np.asarray(loaded(paddle.to_tensor(x))._value),
        np.asarray(m(paddle.to_tensor(x))._value), rtol=1e-6)
