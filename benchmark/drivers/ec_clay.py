"""Driver ``ec_clay``: a Clay pool's device codec with nothing in front.

The deployment is an erasure-coded pool on upstream's Clay plugin; the
work the cell measures is what recovery pays for when an OSD dies: the
lost chunk of every object rebuilt from d helpers, each sending the
1/q of its chunk that ``minimum_to_decode`` names (ECBackend's recovery
read), beside the encode every write pays.  Set-up makes seeded data
``[k, S*C]`` on the device, encodes it through the plugin's codec
(``ec.codec``, the one ``encode_chunks`` and the repair route through),
and gathers the helpers' repair runs into a resident ``[d, S*C/q]``.
The window alternates ``codec.encode_device(data)`` and
``codec.repair_device(helpers, lost)``, two in flight, each ended by
``block_until_ready`` and counted as k*C*S object bytes.  A plugin with
no device codec fails set-up: the host loop would make one device call
per plane and pair, and that is not what the cell measures.

``correct`` compares what the timed calls returned with
``lib/clay_reference.py``: every kept parity output byte for byte with
the reference's encode of the seeded data, every kept repaired chunk
with the data it stood for; and the helpers' runs with the reference's
repair planes, d*C/q bytes a stripe.
"""
from __future__ import annotations

from ..lib import clay_reference
from . import ec_plugin


class Driver(ec_plugin.Driver):
    def __init__(self, config, traffic, seed, run_dir, control=None):
        super().__init__(config, traffic, seed, run_dir, control)
        if len(self.erased) != 1:
            raise ValueError("driver ec_clay repairs one lost chunk")
        self.lost = self.erased[0]
        self.d = int(self.profile["d"])
        self.clay = clay_reference.Clay(self.k, self.m, self.d)

    def setup(self) -> None:
        if self.t["mode"] != "resident":
            raise ValueError("driver ec_clay runs resident traffic only")
        super().setup()

    def _setup_resident(self) -> None:
        import jax
        import jax.numpy as jnp
        stripes = int(self.t["stripes"])
        chunk, k, m = self.chunk, self.k, self.m
        n = stripes * chunk
        codec = getattr(self.ec, "codec", None)
        if codec is None or self.ec.device_codec(k * n) is None:
            raise RuntimeError(
                f"plugin {self.p['plugin']!r} has no device codec for this "
                f"profile: the cell measures the device codec, not the "
                f"host loop")

        @jax.jit
        def make(key):
            return jax.random.bits(key, (k, n), dtype=jnp.uint8)
        self.data = make(jax.random.key(self.seed % (1 << 63)))
        parity = codec.encode_device(self.data, chunk)
        others = set(range(k + m)) - {self.lost}
        runs = self.ec.minimum_to_decode({self.lost}, others)
        self.ids = list(runs)
        self.runs = {h: list(r) for h, r in runs.items()}
        planes = self.ec.get_sub_chunk_count()
        sc = chunk // planes
        picked = jnp.asarray([z for off, cnt in runs[self.ids[0]]
                              for z in range(off, off + cnt)])
        rows = [(self.data[h] if h < k else parity[h - k])
                .reshape(stripes, planes, sc) for h in self.ids]
        self.helpers = jax.block_until_ready(jnp.stack(
            [jnp.take(r, picked, axis=1).reshape(-1) for r in rows]))
        del parity, rows
        self.object_bytes = k * n
        # warm both shapes; outputs dropped
        jax.block_until_ready(codec.encode_device(self.data, chunk))
        jax.block_until_ready(codec.repair_device(
            self.helpers, self.lost, self.ids, chunk))

    def _op(self):
        import jax
        codec, data, helpers = self.ec.codec, self.data, self.helpers
        lost, ids, chunk, kept = self.lost, self.ids, self.chunk, self.kept
        nbytes, block = self.object_bytes, jax.block_until_ready
        depth = int(self.t["in_flight"])
        pending = self.pending = []           # (kind, output) not waited for

        def call(_ci, seq):
            # as ec_plugin's resident loop: enqueue this call, then wait
            # for the oldest one still in flight
            if seq % 2 == 0:
                pending.append(("parity", codec.encode_device(data, chunk)))
            else:
                pending.append(("recovered", codec.repair_device(
                    helpers, lost, ids, chunk)))
            if len(pending) < depth:
                return 0, None
            kind, out = pending.pop(0)
            kept[kind].offer((0, block(out)))
            return nbytes, None
        return call

    def snapshot(self) -> dict:
        from ceph_tpu.common.context import default_context
        snap = super().snapshot()
        snap["counters"] = {
            name: {c: v for c, v in vals.items()
                   if isinstance(v, (int, float))}
            for name, vals in default_context().perf.perf_dump().items()
            if name.startswith("clay")}
        return snap

    def after_window(self) -> dict:
        import numpy as np
        data = np.asarray(self.data)
        full = self.clay.codeword(data, self.chunk)
        seen = {"parity": 0, "recovered": 0}
        wrong = {"parity": 0, "recovered": 0}
        for kind, res in self.kept.items():
            want = full[self.k:] if kind == "parity" else full[self.lost]
            for _bi, out in res.sample():
                got = np.asarray(out)
                seen[kind] += 1
                wrong[kind] += (want.size if got.shape != want.shape
                                else int(np.count_nonzero(got != want)))
        planes = self.clay.repair_planes(self.lost)
        runs_wrong = sum(
            1 for h in self.ids
            if [z for off, cnt in self.runs[h]
                for z in range(off, off + cnt)] != planes)
        read = int(np.prod(self.helpers.shape))
        return {
            "parity_outputs_compared": (seen["parity"], ">=", 1),
            "recovered_outputs_compared": (seen["recovered"], ">=", 1),
            "parity_bytes_wrong": (wrong["parity"], "<=", 0),
            "recovered_bytes_wrong": (wrong["recovered"], "<=", 0),
            "helpers_not_d": (abs(len(self.ids) - self.d), "<=", 0),
            "helper_runs_wrong": (runs_wrong, "<=", 0),
            "helper_bytes_not_d_C_over_q": (
                abs(read - self.d * data.shape[1] // self.clay.q), "<=", 0),
            "ops_never_answered": (len(self.stuck), "<=", 0),
        }

    def close(self) -> None:
        super().close()
        self.helpers = None
