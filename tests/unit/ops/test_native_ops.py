"""Native C++ op tests: op_builder JIT build/load, async IO, CPU Adam.

Pattern: reference ``tests/unit/ops/{aio,adam}`` -- build the extension,
check the op against a pure-python reference.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

from deeperspeed_tpu.op_builder import ALL_OPS, AsyncIOBuilder, CPUAdamBuilder

pytestmark = pytest.mark.skipif(
    not AsyncIOBuilder().is_compatible(),
    reason="no C++ toolchain on this host")


class TestOpBuilder:
    def test_registry_and_build(self):
        assert set(ALL_OPS) >= {"async_io", "cpu_adam", "cpu_adagrad", "cpu_lion"}
        lib = AsyncIOBuilder().load()
        assert lib is not None
        # cached second load is the same object
        assert AsyncIOBuilder().load() is lib

    def test_build_artifact_cached(self):
        b = CPUAdamBuilder()
        p1 = b.build()
        m1 = os.path.getmtime(p1)
        p2 = b.build()
        assert p1 == p2 and os.path.getmtime(p2) == m1


    def test_concurrent_builds_into_an_empty_dir(self, tmp_path):
        """Several processes build the same library at once into an empty
        ``DST_BUILD_DIR`` (xdist workers on a fresh tree): each must load
        it, and none may take another's half-written file."""
        code = ("from deeperspeed_tpu.op_builder import CPUAdamBuilder; "
                "lib = CPUAdamBuilder().load(); "
                "print('loaded', lib.dst_cpu_adam_step is not None)")
        env = dict(os.environ, DST_BUILD_DIR=str(tmp_path / "build"))
        procs = [subprocess.Popen([sys.executable, "-c", code], env=env,
                                  stdout=subprocess.PIPE,
                                  stderr=subprocess.PIPE, text=True)
                 for _ in range(4)]
        for p in procs:
            out, err = p.communicate(timeout=300)
            assert p.returncode == 0, err[-2000:]
            assert "loaded True" in out
        left = sorted(os.listdir(tmp_path / "build"))
        assert len(left) == 1 and left[0].endswith(".so"), left


class TestAsyncIO:
    def test_write_read_roundtrip(self, tmp_path):
        from deeperspeed_tpu.ops.aio import AsyncIOHandle

        h = AsyncIOHandle(num_threads=2)
        rng = np.random.RandomState(0)
        arrays = {f"t{i}": rng.randn(1000 + i).astype(np.float32)
                  for i in range(4)}
        for name, a in arrays.items():
            h.async_pwrite(a, str(tmp_path / name))
        assert h.wait() == 0
        for name, a in arrays.items():
            buf = np.empty(a.nbytes, np.uint8)
            h.async_pread(buf, str(tmp_path / name))
            assert h.wait() == 0
            np.testing.assert_array_equal(buf.view(np.float32), a)
        h.close()

    def test_read_missing_file_reports_error(self, tmp_path):
        from deeperspeed_tpu.ops.aio import AsyncIOHandle

        h = AsyncIOHandle(num_threads=1)
        buf = np.empty(16, np.uint8)
        h.async_pread(buf, str(tmp_path / "nope"))
        assert h.wait() < 0
        h.close()

    def test_bytes_payload(self, tmp_path):
        from deeperspeed_tpu.ops.aio import AsyncIOHandle

        h = AsyncIOHandle()
        payload = b"deeperspeed-tpu checkpoint bytes"
        h.async_pwrite(payload, str(tmp_path / "blob"))
        assert h.wait() == 0
        assert (tmp_path / "blob").read_bytes() == payload
        h.close()


class TestCheckpointEngineAIO:
    def test_async_engine_uses_native_io(self, tmp_path):
        from deeperspeed_tpu.runtime.checkpoint_engine.checkpoint_engine import (
            AsyncCheckpointEngine)

        eng = AsyncCheckpointEngine()
        assert eng._aio is not None  # native path active when toolchain exists
        eng.save(b"abc" * 1000, str(tmp_path / "f1"))
        eng.save(b"xyz" * 500, str(tmp_path / "f2"))
        assert eng.commit("tag0")
        assert eng.load(str(tmp_path / "f1")) == b"abc" * 1000


def _np_adam(p, g, m, v, t, lr, b1, b2, eps, wd, adamw):
    if not adamw and wd > 0:
        g = g + wd * p
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    mh = m / (1 - b1 ** t)
    vh = v / (1 - b2 ** t)
    u = mh / (np.sqrt(vh) + eps)
    if adamw and wd > 0:
        u = u + wd * p
    return p - lr * u, m, v


class TestCPUAdam:
    @pytest.mark.parametrize("adamw", [True, False])
    def test_matches_numpy_reference(self, adamw):
        from deeperspeed_tpu.ops.adam.cpu_adam import DeeperSpeedCPUAdam

        rng = np.random.RandomState(1)
        p = rng.randn(4097).astype(np.float32)
        opt = DeeperSpeedCPUAdam(lr=1e-2, weight_decay=0.01, adamw_mode=adamw)
        p_native = {"w": p.copy()}
        p_ref, m_ref, v_ref = p.copy(), np.zeros_like(p), np.zeros_like(p)
        for t in range(1, 5):
            g = rng.randn(4097).astype(np.float32)
            opt.step(p_native, {"w": g})
            p_ref, m_ref, v_ref = _np_adam(
                p_ref, g, m_ref, v_ref, t, 1e-2, 0.9, 0.999, 1e-8, 0.01, adamw)
            np.testing.assert_allclose(p_native["w"], p_ref, rtol=2e-5, atol=2e-6)

    def test_cpu_lion_and_adagrad_steps(self):
        import ctypes

        lib = CPUAdamBuilder().load()
        rng = np.random.RandomState(2)
        n = 2048
        p = rng.randn(n).astype(np.float32)
        g = rng.randn(n).astype(np.float32)
        m = np.zeros(n, np.float32)
        p_ref = p - 1e-3 * np.sign(0.1 * g)  # b1=0.9, m=0 -> c=(1-b1)*g
        f32p = ctypes.POINTER(ctypes.c_float)
        lib.dst_cpu_lion_step(p.ctypes.data_as(f32p), g.ctypes.data_as(f32p),
                              m.ctypes.data_as(f32p), n,
                              1e-3, 0.9, 0.99, 0.0)
        np.testing.assert_allclose(p, p_ref, rtol=1e-6, atol=1e-7)
        np.testing.assert_allclose(m, 0.01 * g, rtol=1e-5)

        h = np.zeros(n, np.float32)
        p2 = np.ones(n, np.float32)
        g2 = np.full(n, 2.0, np.float32)
        lib.dst_cpu_adagrad_step(p2.ctypes.data_as(f32p), g2.ctypes.data_as(f32p),
                                 h.ctypes.data_as(f32p), n, 0.1, 1e-8, 0.0)
        np.testing.assert_allclose(h, 4.0)
        np.testing.assert_allclose(p2, 1.0 - 0.1 * 2.0 / 2.0, rtol=1e-5)
