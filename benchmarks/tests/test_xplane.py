"""xplane.py on plain planes and on a small trace recorded on the CPU."""

import time

import pytest

import xplane


def test_union_and_busy_arithmetic():
    planes = [
        ("/device:TPU:0", [
            ("XLA Ops", [("a", 0.0, 10.0), ("b", 5.0, 10.0),
                         ("a", 100.0, 20.0), ("zero", 130.0, 0.0)]),
            ("Steps", [("step", 0.0, 1000.0)]),
        ]),
        ("/host:CPU", [("python", [("solve", 16.0, 80.0)])]),
    ]
    r = xplane.reduce_planes(planes, xplane.TPU_PLANE, xplane.TPU_OP_LINES)
    assert r["busy_s"] == pytest.approx(35e-9)      # [0,15] + [100,120]
    assert r["device_planes"] == 1
    ops = dict(map(tuple, r["breakdown"]["device_ops"]))
    assert ops["a"] == pytest.approx(30e-9) and "step" not in ops
    gaps = dict(map(tuple, r["breakdown"]["idle_gaps"]))
    assert gaps == {"host:solve": pytest.approx(85e-9)}


def test_busy_is_the_mean_over_device_planes():
    planes = [("/device:TPU:0", [("XLA Ops", [("a", 0.0, 10.0)])]),
              ("/device:TPU:1", [("XLA Ops", [("a", 0.0, 30.0)])])]
    r = xplane.reduce_planes(planes, xplane.TPU_PLANE, xplane.TPU_OP_LINES)
    assert r["busy_s"] == pytest.approx(20e-9) and r["device_planes"] == 2


@pytest.mark.parametrize("planes", [
    [],
    [("/host:CPU", [("python", [("solve", 0.0, 5.0)])])],
    [("/device:TPU:0", [("XLA Ops", [])])],
    [("/device:TPU:0", [("XLA Ops", [("a", 3.0, 0.0)])])],
    [("/device:TPU:0", [("Steps", [("s", 0.0, 9.0)])])],
])
def test_no_device_events_is_an_error_never_zero(planes):
    with pytest.raises(xplane.NoDeviceEvents):
        xplane.reduce_planes(planes, xplane.TPU_PLANE, xplane.TPU_OP_LINES)


def test_on_a_trace_recorded_on_the_cpu(tmp_path):
    import jax
    import jax.numpy as jnp

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    t0 = time.perf_counter()
    f = jax.jit(lambda a: (a @ a).sum())
    a = jnp.ones((256, 256))
    for _ in range(3):
        f(a).block_until_ready()
        time.sleep(0.02)
    window = time.perf_counter() - t0
    jax.profiler.stop_trace()
    r = xplane.reduce_trace(str(tmp_path), "cpu")
    assert 0 < r["busy_s"] <= window
    assert r["breakdown"]["device_ops"]
    # the same file read for a TPU has no device plane: an error, not 0
    with pytest.raises(xplane.NoDeviceEvents):
        xplane.reduce_trace(str(tmp_path), "tpu")
    with pytest.raises(xplane.NoDeviceEvents):
        xplane.reduce_trace(str(tmp_path / "nothing_here"), "cpu")
