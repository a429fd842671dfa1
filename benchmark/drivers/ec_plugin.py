"""Driver ``ec_plugin``: the codec with nothing in front of it.

The entries are the ones ``ceph_tpu/bench/ec_bench.py`` (the
``ceph_erasure_code_benchmark`` CLI) calls: the plugin registry's
``factory``, then either ``ec.encode`` / ``ec.decode`` on host buffers
(``mode: single``) or ``codec.encode_device`` / ``codec.decode_device``
on a batch resident in HBM (``mode: resident``).  The loop is the
benchmark's own: calls back to back for the window, each ended by the
host bytes being back or by ``block_until_ready``.

``correct`` compares what the timed calls returned, parity and
recovered chunks both, with ``lib/reference.py``.
"""
from __future__ import annotations

import random
import time
from pathlib import Path

import numpy as np

from ..lib import reference
from ..lib.loadgen import Reservoir, closed_loop
from ..lib.stats import Op


class Driver:
    def __init__(self, config: dict, traffic: dict, seed: int,
                 run_dir: Path, control: str | None = None):
        self.p = dict(config["driver_params"])
        self.t = dict(traffic)
        self.seed = seed
        self.control = control
        self.profile = dict(self.p["profile"])
        if control == "technique":
            # the control: another code under the same name.  The
            # configuration states technique=cauchy; the program's own
            # reed_sol_van path gives other parity for the same data
            self.profile["technique"] = "reed_sol_van"
        elif control is not None:
            raise ValueError(f"driver ec_plugin has no control {control!r}")
        self.k, self.m = int(self.profile["k"]), int(self.profile["m"])
        self.erased = sorted(int(e) for e in self.p["erased"])
        self.stuck: list[int] = []
        self.pending: list = []
        if self.p["profile"]["technique"] != "cauchy":
            raise ValueError("the reference knows technique=cauchy only")
        self.parity = reference.cauchy_parity_matrix(self.k, self.m)
        _d, self.src = reference.decode_matrix(self.parity, self.erased)
        # a seeded sample of what the timed calls returned, per kind
        self.kept = {kind: Reservoir(self.t["keep_calls"],
                                     random.Random(seed * 1000003 + salt))
                     for salt, kind in enumerate(("parity", "recovered"))}

    # -- set-up ----------------------------------------------------------------

    def setup(self) -> None:
        from ceph_tpu.plugins.registry import ErasureCodePluginRegistry
        self.ec = ErasureCodePluginRegistry.instance().factory(
            self.p["plugin"], "", dict(self.profile))
        size = int(self.p["size"])
        self.chunk = self.ec.get_chunk_size(size)
        if self.chunk * self.k != size:
            raise ValueError(f"size {size} is not k x chunk ({self.chunk})")
        if self.t["mode"] == "resident":
            self._setup_resident()
        elif self.t["mode"] == "single":
            self._setup_single()
        else:
            raise ValueError(f"driver ec_plugin has no mode {self.t['mode']!r}")

    def _setup_resident(self) -> None:
        import jax
        import jax.numpy as jnp
        stripes = int(self.t["stripes"])
        n = stripes * self.chunk
        k, src = self.k, self.src
        codec = self.ec.codec

        @jax.jit
        def make(key):
            return jax.random.bits(key, (k, n), dtype=jnp.uint8)
        self.data = make(jax.random.key(self.seed % (1 << 63)))
        parity = codec.encode_device(self.data)
        # the survivors the decode reads, in the order decode_matrix
        # names them (the first k of those not erased)
        self.stack = jax.block_until_ready(jnp.stack(
            [self.data[i] if i < k else parity[i - k] for i in src]))
        del parity
        self.object_bytes = k * n
        # warm both shapes; outputs dropped
        jax.block_until_ready(codec.encode_device(self.data))
        jax.block_until_ready(codec.decode_device(self.stack, self.erased))

    def _setup_single(self) -> None:
        size = int(self.p["size"])
        rng = np.random.default_rng([self.seed, 0xec0de])
        self.buffers = [rng.integers(0, 256, size, dtype=np.uint8).tobytes()
                        for _ in range(int(self.t["buffers"]))]
        self.want = set(range(self.k + self.m))
        self.object_bytes = size
        enc = self.ec.encode(self.want, self.buffers[0])
        self.ec.decode(self.want, {i: c for i, c in enc.items()
                                   if i not in self.erased}, 0)

    # -- the window --------------------------------------------------------------

    def _op(self):
        kept, erased, nbytes = self.kept, self.erased, self.object_bytes
        if self.t["mode"] == "resident":
            import jax
            codec, data, stack = self.ec.codec, self.data, self.stack
            block = jax.block_until_ready

            depth = int(self.t["in_flight"])
            pending = self.pending = []       # (kind, output) not waited for

            def call(_ci, seq):
                # enqueue this call, then wait for the oldest one still
                # in flight: with in_flight 2 the device always has the
                # next call queued behind the one it runs.  Every call is
                # ended by block_until_ready and counted when it ends
                if seq % 2 == 0:
                    pending.append(("parity", codec.encode_device(data)))
                else:
                    pending.append(("recovered",
                                    codec.decode_device(stack, erased)))
                if len(pending) < depth:
                    return 0, None
                kind, out = pending.pop(0)
                kept[kind].offer((0, block(out)))
                return nbytes, None
            return call
        ec, want, buffers = self.ec, self.want, self.buffers
        state = {}

        def call(_ci, seq):
            bi = (seq // 2) % len(buffers)
            if seq % 2 == 0:
                state["enc"] = enc = ec.encode(want, buffers[bi])
                kept["parity"].offer((bi, enc))
            else:
                enc = state["enc"]
                dec = ec.decode(want, {i: c for i, c in enc.items()
                                       if i not in erased}, 0)
                kept["recovered"].offer((bi, dec))
            return nbytes, None
        return call

    def window(self, seconds: float, schedule=()):
        ops, errors, stuck, t_start = closed_loop(
            1, seconds, self._op(), schedule)
        # the drain: calls enqueued and not yet waited for
        import jax
        for kind, out in self.pending:
            t0 = time.perf_counter()
            self.kept[kind].offer((0, jax.block_until_ready(out)))
            ops.append(Op(t0, time.perf_counter(), self.object_bytes, True))
        self.pending = []
        self.stuck = stuck
        return ops, errors, t_start

    # -- what the traced run reads -------------------------------------------------

    def snapshot(self) -> dict:
        from ceph_tpu.common.tracer import default_tracer
        hist = default_tracer().histograms()
        return {"spans": {n: (h["sum"], h["count"]) for n, h in hist.items()},
                "rpc": {}, "counters": {}}

    def host_spans(self) -> list:
        return []

    # -- the comparison ------------------------------------------------------------

    def after_window(self) -> dict:
        k, m, erased = self.k, self.m, self.erased
        if self.t["mode"] == "resident":
            data = np.asarray(self.data)
            full = {0: np.concatenate(
                [data, reference.gf_apply(self.parity, data)], axis=0)}
        else:
            full = {}
        seen = {"parity": 0, "recovered": 0}
        wrong = {"parity": 0, "recovered": 0}
        for kind, bi, out in ((kind, bi, out)
                              for kind, res in self.kept.items()
                              for bi, out in res.sample()):
            if bi not in full:
                full[bi] = reference.object_shards(
                    np.frombuffer(self.buffers[bi], dtype=np.uint8),
                    k, self.parity, self.chunk)
            ref = full[bi]
            rows = list(range(k, k + m)) if kind == "parity" else erased
            if isinstance(out, dict):         # plugin interface: {chunk: bytes}
                got = np.stack([np.asarray(out[i], dtype=np.uint8)
                                for i in rows])
            else:                             # codec: rows in that order
                got = np.asarray(out)
            seen[kind] += 1
            if got.shape != ref[rows].shape:
                wrong[kind] += ref[rows].size
            else:
                wrong[kind] += int(np.count_nonzero(got != ref[rows]))
        return {
            "parity_outputs_compared": (seen["parity"], ">=", 1),
            "recovered_outputs_compared": (seen["recovered"], ">=", 1),
            "parity_bytes_wrong": (wrong["parity"], "<=", 0),
            "recovered_bytes_wrong": (wrong["recovered"], "<=", 0),
            "ops_never_answered": (len(self.stuck), "<=", 0),
        }

    def close(self) -> None:
        self.kept = None
        self.data = self.stack = self.ec = None
