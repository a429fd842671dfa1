"""Compile for a TPU v5e without one: what only a TPU lowering can refuse.

The installed libtpu compiles ahead of time against a *described*
topology, so Mosaic's block-shape rules, its i64 legalisation, the
scoped-VMEM limit and ``shard_map``'s varying-axes check are all
reachable from a CPU-only test run.  Every shape here was refused by the
compiler before ISSUE 21 (CPU tests take the XLA path and never saw it):

- Pallas index maps returning Python ints trace as i64 under
  ``jax_enable_x64`` (which CRUSH bulk mapping switches on process-wide);
- the vertical kernel's ``(groups*k, tile)`` / ``(groups*r, tile)`` blocks
  at a fixed ``groups=4`` break the 8-sublane rule for r=1, r=3, odd k;
- ``xor_apply_pallas`` at ``[64, 128]`` overran scoped VMEM;
- ``shard_map`` over the Pallas call wants a ``vma`` on the out shape.

The recipe (also in README "Testing" and the verify skill):
``TPU_ACCELERATOR_TYPE=v5litepod-4 TPU_WORKER_HOSTNAMES=localhost
TPU_SKIP_MDS_QUERY=1`` then ``topologies.get_topology_desc("v5e:2x2",
"tpu")`` and ``jit(f).lower(ShapeDtypeStruct(..., sharding=...))
.compile()``.  The selector sees tracers here, so ``_runs_on_tpu`` — which
asks the runtime's default device — is patched to the answer a TPU host
gives; everything after it is the production path.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from ceph_tpu.gf import cauchy1
from ceph_tpu.ops import pallas_kernels, rs_kernels

STRIPES = 64
# upstream's bench.sh grid + the metric of record + the plugin default
GRID = [(2, 1), (3, 2), (4, 2), (4, 3), (6, 2), (6, 3), (6, 4), (10, 3),
        (10, 4), (8, 4), (8, 3), (7, 3)]


@pytest.fixture(scope="module")
def v5e():
    """The four TpuDevices of a described v5e 2x2, or a clean skip."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_ACCELERATOR_TYPE", "v5litepod-4")
        mp.setenv("TPU_WORKER_HOSTNAMES", "localhost")
        mp.setenv("TPU_SKIP_MDS_QUERY", "1")
        try:
            from jax.experimental import topologies
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2")
        except Exception as e:      # noqa: BLE001 — no libtpu, no test
            pytest.skip(f"cannot describe a v5e topology here: {e!r}")
        yield list(topo.devices)


@pytest.fixture
def as_tpu_host(monkeypatch):
    monkeypatch.setattr(rs_kernels, "_runs_on_tpu", lambda data: True)


def _compile(fn, *args) -> str:
    """AOT-compile ``fn`` for the args' (TPU) sharding; returns the
    lowered text.  Any compiler refusal raises."""
    lowered = jax.jit(fn).lower(*args)
    lowered.compile()
    return lowered.as_text()


def _sds(dev, shape, dtype=jnp.uint8):
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=SingleDeviceSharding(dev))


def _chunk(k: int) -> int:
    return (1 << 20) // k // 128 * 128      # 1 MiB stripes, lane-aligned


def _vertical(dev, k, r):
    return _compile(
        lambda M, D: rs_kernels.gf_apply_stripes(M, D, STRIPES, "auto"),
        _sds(dev, (r, k)), _sds(dev, (STRIPES * k, _chunk(k))))


def _horizontal(dev, k, r):
    return _compile(lambda M, D: rs_kernels.gf_apply(M, D, "auto"),
                    _sds(dev, (r, k)), _sds(dev, (k, STRIPES * _chunk(k))))


def _mesh_encode(devices, shape):
    from ceph_tpu.parallel.mesh import sharded_batch_encode_step
    mesh = Mesh(np.array(devices).reshape(shape), ("dp", "sp"))
    dp, sp = shape
    step = sharded_batch_encode_step(mesh, cauchy1(8, 4))
    lowered = step.lower(jax.ShapeDtypeStruct(
        (8 * dp, 8, 2048 * sp), jnp.uint8,
        sharding=NamedSharding(mesh, P("dp", None, "sp"))))
    lowered.compile()
    return lowered.as_text()


@pytest.mark.parametrize("kernel", [_vertical, _horizontal])
def test_metric_of_record_lowers_to_the_pallas_kernel(v5e, as_tpu_host,
                                                      kernel):
    """RS(8,4) at 64 stripes x 1 MiB through the selector under "auto":
    the lowered text carries the Mosaic custom call — which kernel ran is
    proved, not assumed (the check test_kernel_dispatch could only run on
    real hardware, where no test run ever happened)."""
    with jax.enable_x64(False):
        assert "tpu_custom_call" in kernel(v5e[0], 8, 4)


@pytest.mark.parametrize("k,r", [
    (8, 1),      # single-erasure decode — every profile needs it
    (10, 3),     # m=3 encode
    (7, 3),      # the plugin's default profile: odd k, odd r
])
def test_vertical_kernel_lowers_off_the_even_corners(v5e, as_tpu_host,
                                                     k, r):
    with jax.enable_x64(False):
        assert "tpu_custom_call" in _vertical(v5e[0], k, r)


@pytest.mark.parametrize("x64", [False, True])
@pytest.mark.parametrize("r,k,n", [
    (4, 8, 33554432),    # ec_resident_b256: 256 stripes x 1 MiB, encode
    (2, 8, 33554432),    # ... and its two-erasure decode
    (4, 8, 524288),      # a served 4 MiB put
    (4, 8, 8192),        # a served 64 KiB put at a 4 KiB stripe unit (two
    (4, 8, 16384),       # stripes), and the buckets two and four of them
    (4, 8, 32768),       # coalesce into (rados_write_64k_qd64)
    (1, 8, 4096),        # off the even corners, as the vertical kernel
    (3, 10, 4096),       # is tested above: odd r, k no multiple of 4,
    (3, 7, 4096),        # chunks of 4 KiB
    (2, 4, 4096),
])
def test_horizontal_kernel_lowers(v5e, r, k, n, x64):
    """Column groups stacked on the sublanes, planes from packed words,
    one block-diagonal int8 matmul: the scoped-VMEM limit at the
    widest grid step and Mosaic's block rules at every (k, r), with the
    i64 constants a placing process would trace."""
    with jax.enable_x64(x64):
        text = _compile(pallas_kernels.gf_apply_pallas,
                        _sds(v5e[0], (r, k)), _sds(v5e[0], (k, n)))
    assert "tpu_custom_call" in text


def test_pallas_lowers_with_x64_on(v5e, as_tpu_host):
    """A process that places PGs has x64 on; it must still encode."""
    with jax.enable_x64(True):
        assert "tpu_custom_call" in _horizontal(v5e[0], 8, 4)
        assert "tpu_custom_call" in _vertical(v5e[0], 8, 1)


def test_xor_kernel_fits_scoped_vmem_at_w32(v5e):
    """w=32 reed_sol (k=4, m=2) is a [64, 128] bitmatrix: the shape the
    kernel's docstring promises."""
    with jax.enable_x64(False):
        _compile(pallas_kernels.xor_apply_pallas,
                 _sds(v5e[0], (64, 128), jnp.int8),
                 _sds(v5e[0], (128, 1 << 20)))


def test_shard_map_over_pallas_lowers_on_2x2(v5e, as_tpu_host):
    with jax.enable_x64(False):
        assert "tpu_custom_call" in _mesh_encode(v5e, (2, 2))


@pytest.mark.parametrize("r,n", [(12, 524288), (12, 8192), (6, 777)])
@pytest.mark.parametrize("given", ["host_words", "device_bytes"])
def test_crc32c_rows_lowers(v5e, r, n, given):
    """The HashInfo checksum at the served shard sizes (4 MiB and 64 KiB
    objects on k=8 m=4) and an odd one, both ways ``crc32c_rows`` enters
    the device: words viewed on the host, bytes made words on the chip."""
    pad = rs_kernels._crc_pad(n)
    with jax.enable_x64(False):
        if given == "host_words":
            lowered = rs_kernels._crc32c_words_jit.lower(_sds(
                v5e[0], (r, *rs_kernels._crc_words_shape(pad)), jnp.uint32))
        else:
            lowered = rs_kernels._crc32c_rows_jit.lower(
                _sds(v5e[0], (r, n)), pad)
        text = lowered.compile().as_text()
    assert "gather" not in text and "dynamic-slice" not in text


def test_encode_with_crc_lowers_at_the_served_shape(v5e, as_tpu_host):
    """The fused encode + checksum dispatch, k=8 m=4 on 512 KiB shards:
    the Pallas encode and the word fold in one program."""
    with jax.enable_x64(False):
        lowered = rs_kernels._gf_encode_with_crc_jit.lower(
            _sds(v5e[0], (4, 8)), _sds(v5e[0], (8, 524288)), "auto", 524288)
        text = lowered.compile().as_text()
    assert "tpu_custom_call" in lowered.as_text()
    # (gf_apply_pallas expands its [4, 8] matrix with a gather of its own)
    crc_ops = [ln for ln in text.splitlines() if "ceph.crc32c_rows" in ln]
    assert crc_ops and not any("gather(" in ln or "dynamic-slice(" in ln
                               for ln in crc_ops)


@pytest.mark.slow
@pytest.mark.parametrize("x64", [False, True])
def test_every_profile_shape_lowers(v5e, as_tpu_host, x64):
    """The whole (k, m) grid, each with r in {m, 1, 2}, both kernels,
    with and without x64; the xor kernel; shard_map on 2x2 and 4x1."""
    with jax.enable_x64(x64):
        for k, m in GRID:
            for r in sorted({m, 1, 2} & set(range(1, m + 1))):
                assert "tpu_custom_call" in _vertical(v5e[0], k, r), (k, m, r)
                # gf_apply sends matrices under 8 coefficients to the
                # VPU lookup path by design: that must compile too
                assert ("tpu_custom_call" in _horizontal(v5e[0], k, r)) \
                    == (r * k >= 8), (k, m, r)
        for rk in ((64, 128), (14, 28)):
            _compile(pallas_kernels.xor_apply_pallas,
                     _sds(v5e[0], rk, jnp.int8),
                     _sds(v5e[0], (rk[1], 1 << 20)))
        for shape in ((2, 2), (4, 1)):
            assert "tpu_custom_call" in _mesh_encode(v5e, shape)


@pytest.mark.slow
def test_crush_bulk_kernel_compiles_for_tpu(v5e):
    """The placement kernel (x64 fixed-point straw2) for a 6-wide
    chooseleaf-indep rule over 256 OSDs — minutes of compile."""
    from ceph_tpu.crush.jax_mapper import BulkMapper
    from ceph_tpu.crush.map import (CRUSH_BUCKET_STRAW2,
                                    CRUSH_RULE_CHOOSELEAF_INDEP,
                                    CRUSH_RULE_EMIT, CRUSH_RULE_TAKE,
                                    CrushMap)
    cmap = CrushMap()
    cmap.set_type_name(1, "host")
    cmap.set_type_name(2, "root")
    hosts = [cmap.add_bucket(CRUSH_BUCKET_STRAW2, 1,
                             list(range(h, h + 8)), [0x10000] * 8)
             for h in range(0, 256, 8)]
    root = cmap.add_bucket(CRUSH_BUCKET_STRAW2, 2, hosts,
                           [8 * 0x10000] * len(hosts))
    cmap.finalize()
    ruleno = cmap.add_rule([(CRUSH_RULE_TAKE, root, 0),
                            (CRUSH_RULE_CHOOSELEAF_INDEP, 6, 1),
                            (CRUSH_RULE_EMIT, 0, 0)])
    bulk = BulkMapper(cmap)
    with jax.enable_x64(True):
        _compile(lambda xs: bulk.map_rule(ruleno, xs, result_max=6),
                 _sds(v5e[0], (32768,), jnp.uint32))
