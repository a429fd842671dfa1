"""Device-mesh sharding of codec batches.

The TPU-native equivalent of the reference's cluster fan-out: where Ceph's
primary OSD fans ECSubWrites out to shard OSDs over the async messenger
(reference: src/osd/ECBackend.cc:2036-2070), a multi-chip TPU deployment
shards the stripe batch over a `jax.sharding.Mesh` and lets XLA insert ICI
collectives (SURVEY.md §5 "distributed communication backend").

Mesh axes:
  dp   data parallel over stripes  — independent stripes on different chips
  sp   "sequence" parallel over chunk bytes — one huge stripe split along
       its byte axis (the long-context analog: stripes too big for one chip)

The encode step runs the GF(2) bitslice matmul on each chip's local block,
then reduces a placement checksum over sp (psum) and rotates parity shards
around the dp ring (ppermute) the way the primary hands sub-writes to its
peers.  All collectives ride ICI; nothing touches the host.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map as _shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..ops import rs_kernels


def make_mesh(n_devices: int | None = None, dp: int | None = None) -> Mesh:
    """A (dp, sp) mesh over the first n_devices devices."""
    devices = jax.devices()
    n = n_devices or len(devices)
    if n > len(devices):
        # an undersized reshape below would raise an opaque numpy error;
        # name the real problem (the serving pipeline gates on this
        # before building a mesh, ad-hoc callers may not)
        raise ValueError(
            f"mesh wants {n} devices, only {len(devices)} present")
    if dp is None:
        dp = 1
        for cand in range(int(np.sqrt(n)), 0, -1):
            if n % cand == 0:
                dp = cand
                break
    sp = n // dp
    arr = np.array(devices[:n]).reshape(dp, sp)
    return Mesh(arr, axis_names=("dp", "sp"))


def sharded_encode_step(mesh: Mesh, parity_mat: np.ndarray):
    """Build a jit'd multi-chip encode step.

    Returns step(data) where data is [B, k, N] uint8, sharded
    [B@dp, k, N@sp].  Output: (parity [B, m, N] with the same sharding,
    checksum [B] int32 psum'd over sp, rotated parity from the dp ring).
    """
    mat = jnp.asarray(parity_mat, dtype=jnp.uint8)
    m, k = parity_mat.shape

    def local_step(data_blk):
        # data_blk: [B/dp, k, N/sp] on this chip.  Restack into the
        # VERTICAL stripe layout and run the PRODUCTION kernel selector
        # (gf_apply_stripes: pallas on TPU, XLA bitslice elsewhere) — the
        # single-chip bench and the sharded path must exercise ONE kernel,
        # so shard_map-over-pallas is exactly what multi-chip runs.
        b, kk, n = data_blk.shape
        vert = data_blk.reshape(b * kk, n)
        parity = rs_kernels.gf_apply_stripes(mat, vert, b)
        parity = parity.reshape(b, m, n)                    # [B/dp, m, N/sp]
        # placement checksum: reduce over the byte axis, then over sp —
        # the integrity cross-check a deep-scrub would do per shard
        # (reference: src/osd/ECBackend.cc:2461 be_deep_scrub crc recompute)
        local_sum = parity.astype(jnp.int32).sum(axis=(1, 2))
        checksum = jax.lax.psum(local_sum, axis_name="sp")
        # sub-write fan-out analog: hand this chip's parity to the next
        # dp-ring neighbour (primary -> shard OSD hop over ICI)
        ndp = jax.lax.psum(1, axis_name="dp")
        rotated = jax.lax.ppermute(
            parity, axis_name="dp",
            perm=[(i, (i + 1) % ndp) for i in range(ndp)])
        return parity, checksum, rotated

    step = _shard_map(
        local_step, mesh=mesh,
        in_specs=(P("dp", None, "sp"),),
        out_specs=(P("dp", None, "sp"), P("dp"), P("dp", None, "sp")))
    return jax.jit(step)


def sharded_batch_encode_step(mesh: Mesh, parity_mat: np.ndarray):
    """Parity-only multi-chip encode for the SERVING batch path: the same
    dp/sp sharding and production kernel selector as
    :func:`sharded_encode_step`, WITHOUT the placement checksum psum and
    the dp-ring ppermute — those model scrub/fan-out for the MULTICHIP
    dryrun, and a serving dispatch that discards them would still pay
    their ICI traffic (jitted outputs cannot be dead-code-eliminated).

    Returns step(data [B, k, N] sharded [B@dp, k, N@sp]) -> parity
    [B, m, N], same sharding.
    """
    mat = jnp.asarray(parity_mat, dtype=jnp.uint8)
    m, _k = parity_mat.shape

    def local_step(data_blk):
        b, kk, n = data_blk.shape
        vert = data_blk.reshape(b * kk, n)
        parity = rs_kernels.gf_apply_stripes(mat, vert, b)
        return parity.reshape(b, m, n)

    step = _shard_map(local_step, mesh=mesh,
                      in_specs=(P("dp", None, "sp"),),
                      out_specs=P("dp", None, "sp"))
    return jax.jit(step)


def sharded_decode_step(mesh: Mesh):
    """Distributed reconstruction: survivors sharded over chips, partial
    GF products reduced over ICI.

    The reference rebuilds a lost shard by pulling chunks from helper OSDs
    over the messenger and combining them on the primary
    (src/osd/ECBackend.cc:565-732 recovery, clay's fractional helper reads).
    The TPU-native shape: survivor chunks live chunk-sharded on the mesh's
    dp axis; each chip applies its columns of the decode matrix to its
    local chunks (a partial GF(2^8) product = XOR-accumulable), and one
    ``psum`` over the axis IS the helper->rebuilder transfer, riding ICI.
    GF addition is XOR, which is exactly bitwise-reduce-able: psum over
    bit-planes mod 2 keeps the math exact.

    Returns step(D, chunks) with D [r, n_survivors] uint8 (replicated) and
    chunks [n_survivors, N] uint8 sharded [n@dp, N@sp]; output [r, N]
    sharded [None, N@sp] (fully reconstructed on every dp row).  Survivor
    counts that don't divide over dp are zero-padded internally (zero
    chunks contribute nothing to the XOR sum).
    """
    ndp = mesh.shape["dp"]

    def local_step(D_blk, chunks_blk):
        # D_blk: [r, n/dp] this chip's columns; chunks_blk: [n/dp, N/sp]
        partial = rs_kernels.gf_apply_lookup(D_blk, chunks_blk)  # [r, N/sp]
        # XOR-reduce over dp: unpack to bit-planes, psum, mod 2, repack —
        # exact because XOR == addition mod 2 per bit; the per-bit sum is
        # bounded by ndp, so uint16 keeps the ICI payload small
        bits = jnp.unpackbits(partial, axis=0, bitorder="little")
        summed = jax.lax.psum(bits.astype(jnp.uint16), axis_name="dp")
        rec_bits = (summed & 1).astype(jnp.uint8)
        return jnp.packbits(rec_bits, axis=0, bitorder="little")

    jitted = jax.jit(_shard_map(
        local_step, mesh=mesh,
        in_specs=(P(None, "dp"), P("dp", "sp")),
        out_specs=P(None, "sp")))

    def step(D, chunks):
        D = jnp.asarray(D, dtype=jnp.uint8)
        chunks = jnp.asarray(chunks, dtype=jnp.uint8)
        n = chunks.shape[0]
        if D.shape[1] != n:
            raise ValueError(
                f"D has {D.shape[1]} columns for {n} survivor chunks")
        pad = (-n) % ndp
        if pad:
            D = jnp.pad(D, ((0, 0), (0, pad)))
            chunks = jnp.pad(chunks, ((0, pad), (0, 0)))
        return jitted(D, chunks)
    return step


def sharded_placement_step(mesh: Mesh, bulk, ruleno: int, n_osds: int,
                           reweights=None, result_max: int = 0):
    """Distributed bulk placement: the multi-chip ParallelPGMapper.

    The reference maps every PG of every pool on a host thread pool
    (reference: src/osd/OSDMapMapping.h:18 ParallelPGMapper); here the
    placement-seed vector shards over the ``dp`` axis, every device runs
    the jitted CRUSH kernel on its block, and the per-OSD utilization
    histogram — what the mon's mapping job exists to produce — reduces
    over the ICI ring with ONE psum.  Returns
    ``step(xs [N]) -> (out [N, numrep] dp-sharded, hist [n_osds]
    replicated)``.
    """
    CRUSH_ITEM_NONE = 0x7FFFFFFF

    def local(xs_blk):
        out, placed = bulk.map_rule(ruleno, xs_blk,
                                    reweights=reweights,
                                    result_max=result_max)
        # holes are CRUSH_ITEM_NONE (a positive int32): mask them like
        # every host consumer does, or they corrupt the scatter index
        valid = (out >= 0) & (out != CRUSH_ITEM_NONE)
        hist = jnp.zeros((n_osds,), jnp.int32).at[
            jnp.where(valid, out, 0)].add(valid.astype(jnp.int32))
        hist = jax.lax.psum(hist, axis_name="dp")     # ICI all-reduce
        return out, hist

    # Disable the varying-axes checker: the CRUSH kernel's bounded-retry
    # loops initialise carries from literals (unvarying) and update them
    # from the dp-varying seeds — sound, but unprovable for the checker.
    return jax.jit(_shard_map(local, mesh=mesh,
                              in_specs=(P("dp"),),
                              out_specs=(P("dp"), P(None)),
                              check_vma=False))
