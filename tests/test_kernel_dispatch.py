"""Which-kernel-executed guards.

Round-4 postmortem: `_runs_on_tpu` once mapped the ConcretizationTypeError a Tracer raises
from `.devices()` to "not TPU", so every JITTED caller — including the
bench chain — silently took the XLA bitslice fallback instead of the
pallas kernel, and the bench quietly measured the wrong kernel.  These
tests pin the dispatch contract so that failure mode cannot recur:

- under jit trace on a TPU-default backend, `_runs_on_tpu` is True;
- a jitted caller at bench-like shapes actually INVOKES the pallas
  kernel (recorded via monkeypatch, executed in interpret mode on CPU);
- the sharded multichip step routes through the SAME production
  selector (`gf_apply_stripes`) as the single-chip bench.

That the selector's TPU branch really lowers to the Mosaic custom call is
checked against a described v5e in tests/test_tpu_lowering.py.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ceph_tpu.gf import cauchy1, ref
from ceph_tpu.ops import pallas_kernels, rs_kernels

K, M, S, N = 8, 4, 8, 1024      # bench-like: n >= 1024 engages pallas


class _FakeTpuDevice:
    platform = "tpu"

    def __repr__(self):
        return "FakeTpuDevice"


@pytest.fixture
def fake_tpu(monkeypatch):
    """Make the runtime LOOK like a TPU host without real hardware: the
    default-device probe reports tpu, and the pallas kernel runs in
    interpret mode so it executes on CPU."""
    monkeypatch.setattr(jax, "devices",
                        lambda *a, **k: [_FakeTpuDevice()])
    orig = pallas_kernels.gf_apply_stripes_pallas
    calls: list = []

    def recording(mat, data, stripes, **kw):
        calls.append(stripes)
        kw["interpret"] = True
        return orig(mat, data, stripes, **kw)
    monkeypatch.setattr(pallas_kernels, "gf_apply_stripes_pallas",
                        recording)
    return calls


def test_runs_on_tpu_true_under_trace(fake_tpu):
    """A Tracer has no committed device; the probe MUST fall through to
    the runtime default platform, not report 'not TPU'."""
    seen = []

    def f(x):
        seen.append(rs_kernels._runs_on_tpu(x))
        return x + 1
    jax.jit(f)(jnp.zeros((4, 4), jnp.uint8))
    assert seen == [True]


def test_jitted_caller_invokes_pallas(fake_tpu):
    """The bench's jitted apply at bench shapes must reach the pallas
    kernel — and its output must bit-match the XLA fallback."""
    rng = np.random.default_rng(7)
    mat = cauchy1(K, M)
    data = rng.integers(0, 256, size=(S * K, N), dtype=np.uint8)

    out = jax.jit(
        lambda Mt, D: rs_kernels.gf_apply_stripes(Mt, D, S))(
            jnp.asarray(mat), jnp.asarray(data))
    assert fake_tpu == [S], "jitted caller did not reach the pallas kernel"
    want = np.concatenate([ref.encode(mat, data[s * K:(s + 1) * K])
                           for s in range(S)], axis=0)
    np.testing.assert_array_equal(np.asarray(out), want)


def test_cpu_committed_array_takes_xla_fallback(fake_tpu):
    """Eager callers with CPU-COMMITTED concrete arrays must stay on the
    XLA path even on a TPU host (the Mosaic kernel cannot lower on CPU;
    the committed device wins — _runs_on_tpu's documented contract)."""
    rng = np.random.default_rng(8)
    mat = cauchy1(K, M)
    data = rng.integers(0, 256, size=(S * K, N), dtype=np.uint8)
    out = rs_kernels.gf_apply_stripes(mat, data, S)   # asarray commits CPU
    assert fake_tpu == [], "CPU-committed data must not hit the TPU kernel"
    want = np.concatenate([ref.encode(mat, data[s * K:(s + 1) * K])
                           for s in range(S)], axis=0)
    np.testing.assert_array_equal(np.asarray(out), want)


def test_sharded_step_routes_through_production_selector(monkeypatch):
    """The multichip encode must call gf_apply_stripes (the SAME selector
    the bench uses: pallas on TPU, bitslice elsewhere) — not a private
    kernel of its own (round-4 weakness #2)."""
    from ceph_tpu.parallel.mesh import make_mesh, sharded_encode_step

    calls: list = []
    orig = rs_kernels.gf_apply_stripes

    def recording(mat, data, stripes, *a, **kw):
        calls.append(stripes)
        return orig(mat, data, stripes, *a, **kw)
    monkeypatch.setattr(rs_kernels, "gf_apply_stripes", recording)

    mesh = make_mesh(8)
    dp, sp = mesh.shape["dp"], mesh.shape["sp"]
    pm = cauchy1(K, M)
    step = sharded_encode_step(mesh, pm)
    rng = np.random.default_rng(9)
    data = rng.integers(0, 256, size=(2 * dp, K, 128 * sp), dtype=np.uint8)
    parity, _, _ = step(data)
    assert calls, "sharded_encode_step bypassed gf_apply_stripes"
    for b in range(data.shape[0]):
        np.testing.assert_array_equal(np.asarray(parity[b]),
                                      ref.encode(pm, data[b]))
