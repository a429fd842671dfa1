"""Quick tile_n sweep for gf_apply_stripes_pallas on live TPU (the group
count follows from the shape: pallas_kernels._stripe_groups).

Uses bench.py's chain-difference timing so numbers are comparable to the
north-star metric.  Dev tool, not part of the suite.
"""
import functools
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from bench import per_op_seconds  # noqa: E402


def main():
    import jax
    import jax.numpy as jnp
    from ceph_tpu.ops import RSCodec
    from ceph_tpu.ops.pallas_kernels import gf_apply_stripes_pallas

    k, m, batch = 8, 4, 64
    n = 1024 * 1024 // k
    rng = np.random.default_rng(0)
    data = rng.integers(0, 256, size=(batch * k, n), dtype=np.uint8)
    codec = RSCodec(k, m, technique="cauchy", device="jax")
    dev = jax.device_put(jnp.asarray(data))
    pmat = jax.device_put(jnp.asarray(codec.parity_mat))
    D, _ = codec.decode_matrix([0, 9])
    dmat = jax.device_put(jnp.asarray(D))

    for tile in (2048, 4096, 8192):
        fn = functools.partial(
            gf_apply_stripes_pallas, stripes=batch, tile_n=tile)

        def ap(M, Dd, _fn=fn):
            return _fn(M, Dd)

        enc = batch / per_op_seconds(ap, pmat, dev)
        dec = batch / per_op_seconds(ap, dmat, dev)
        print(f"t={tile}: encode {enc:8.0f} decode {dec:8.0f} MiB/s",
              flush=True)


if __name__ == "__main__":
    main()
