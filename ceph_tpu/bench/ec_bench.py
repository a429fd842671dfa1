"""ceph_erasure_code_benchmark-compatible CLI.

Flag and output parity with the reference harness
(reference: src/test/erasure-code/ceph_erasure_code_benchmark.cc:40-139):
``--plugin --workload --size --iterations --erasures --erased
--erasures-generation --parameter k=v``; output is one line
``<elapsed_seconds>\t<iterations * size/1024 KiB>`` (:179,310), so
MiB/s = (KiB/1024)/seconds exactly as qa/workunits/erasure-code/bench.sh
computes it.

TPU-specific extensions (off by default; defaults match the reference):
  --batch B      encode/decode B stripes per device dispatch through the
                 plugin codec (the ECBackend-style cross-stripe batching
                 the per-stripe reference loop cannot do, SURVEY.md §3.2)
  --device-resident   keep buffers on device between iterations (models the
                 sidecar's persistent device buffers; excludes the
                 host<->device transfer from the timed loop)
  --directory    plugin directory (erasure_code_dir analog)
"""
from __future__ import annotations

import argparse
import random
import sys
import time

import numpy as np

from ..plugins.registry import ErasureCodePluginRegistry


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="ec_bench", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument("-v", "--verbose", action="store_true")
    p.add_argument("-s", "--size", type=int, default=1024 * 1024,
                   help="size of the buffer to be encoded")
    p.add_argument("-i", "--iterations", type=int, default=1)
    p.add_argument("-p", "--plugin", default="jerasure")
    p.add_argument("-w", "--workload", choices=["encode", "decode"],
                   default="encode")
    p.add_argument("-e", "--erasures", type=int, default=1)
    p.add_argument("--erased", type=int, action="append", default=[])
    p.add_argument("-E", "--erasures-generation", dest="erasures_generation",
                   choices=["random", "exhaustive"], default="random")
    p.add_argument("-P", "--parameter", action="append", default=[],
                   metavar="KEY=VALUE")
    p.add_argument("--directory", default="")
    p.add_argument("--batch", type=int, default=1)
    p.add_argument("--device-resident", dest="device_resident",
                   action="store_true")
    return p


class ErasureCodeBench:
    def __init__(self, args: argparse.Namespace):
        self.args = args
        self.profile = {}
        for kv in args.parameter:
            if kv.count("=") != 1:
                print(f"--parameter {kv} ignored because it does not contain "
                      f"exactly one =", file=sys.stderr)
                continue
            key, value = kv.split("=")
            self.profile[key] = value
        self.k = int(self.profile.get("k", "7"))
        self.m = int(self.profile.get("m", "3"))

    def _factory(self):
        registry = ErasureCodePluginRegistry.instance()
        return registry.factory(self.args.plugin, self.args.directory,
                                self.profile)

    def _input(self) -> bytes:
        return b"X" * self.args.size

    # -- encode (reference :151-181) ---------------------------------------

    def encode(self) -> int:
        ec = self._factory()
        data = self._input()
        want = set(range(ec.get_chunk_count()))
        if self.args.batch > 1 or self.args.device_resident:
            return self._encode_batched(ec, data)
        begin = time.perf_counter()
        for _ in range(self.args.iterations):
            ec.encode(want, data)
        elapsed = time.perf_counter() - begin
        print(f"{elapsed:.6f}\t{self.args.iterations * (self.args.size // 1024)}")
        return 0

    def _encode_batched(self, ec, data: bytes) -> int:
        import jax
        import jax.numpy as jnp
        batch = self.args.batch
        prepared = ec.encode_prepare(data)
        k = ec.get_data_chunk_count()
        stripe = np.stack([prepared[ec.chunk_index(i)] for i in range(k)])
        folded = np.broadcast_to(stripe, (batch,) + stripe.shape)
        folded = np.ascontiguousarray(
            folded.swapaxes(0, 1).reshape(k, batch * stripe.shape[1]))
        codec = ec.codec
        if self.args.device_resident:
            dev = jax.device_put(jnp.asarray(folded))
            codec.encode_device(dev).block_until_ready()   # warm/compile
            begin = time.perf_counter()
            for _ in range(self.args.iterations):
                codec.encode_device(dev).block_until_ready()
            elapsed = time.perf_counter() - begin
        else:
            codec.encode(folded)                            # warm/compile
            begin = time.perf_counter()
            for _ in range(self.args.iterations):
                codec.encode(folded)
            elapsed = time.perf_counter() - begin
        kib = self.args.iterations * batch * (self.args.size // 1024)
        print(f"{elapsed:.6f}\t{kib}")
        return 0

    # -- decode (reference :246-311) ---------------------------------------

    def decode(self) -> int:
        ec = self._factory()
        data = self._input()
        n = ec.get_chunk_count()
        want = set(range(n))
        encoded = ec.encode(want, data)
        if self.args.erased:
            for i in self.args.erased:
                encoded.pop(i, None)

        if self.args.batch > 1 or self.args.device_resident:
            return self._decode_batched(ec, encoded)

        begin = time.perf_counter()
        for _ in range(self.args.iterations):
            if self.args.erasures_generation == "exhaustive":
                code = self._decode_exhaustive(ec, encoded, encoded, 0,
                                               self.args.erasures)
                if code:
                    return code
            elif self.args.erased:
                ec.decode(want, encoded, 0)
            else:
                chunks = dict(encoded)
                for _ in range(self.args.erasures):
                    while True:
                        erasure = random.randrange(n)
                        if erasure in chunks:
                            break
                    del chunks[erasure]
                ec.decode(want, chunks, 0)
        elapsed = time.perf_counter() - begin
        print(f"{elapsed:.6f}\t{self.args.iterations * (self.args.size // 1024)}")
        return 0

    def _decode_exhaustive(self, ec, all_chunks, chunks, i, want_erasures) -> int:
        """Try all erasure combinations, verifying content
        (reference decode_erasures :200-245)."""
        if want_erasures == 0:
            want_to_read = set(range(ec.get_chunk_count())) - set(chunks)
            decoded = ec.decode(want_to_read, chunks, 0)
            for chunk in want_to_read:
                if not np.array_equal(decoded[chunk], all_chunks[chunk]):
                    print(f"chunk {chunk} content and recovered content are "
                          f"different", file=sys.stderr)
                    return -1
            return 0
        for j in range(i, ec.get_chunk_count()):
            if j not in chunks:
                continue
            one_less = dict(chunks)
            del one_less[j]
            code = self._decode_exhaustive(ec, all_chunks, one_less, j + 1,
                                           want_erasures - 1)
            if code:
                return code
        return 0

    def _decode_batched(self, ec, encoded) -> int:
        n = ec.get_chunk_count()
        erased = self.args.erased or \
            sorted(random.sample(range(n), self.args.erasures))
        src = [i for i in range(n) if i not in erased][:ec.get_data_chunk_count()]
        stripe = np.stack([encoded[i] for i in src])
        batch = np.broadcast_to(stripe, (self.args.batch,) + stripe.shape)
        batch = np.ascontiguousarray(batch)
        codec = ec.codec
        codec.decode_batch(batch, src, erased)              # warm/compile
        begin = time.perf_counter()
        for _ in range(self.args.iterations):
            codec.decode_batch(batch, src, erased)
        elapsed = time.perf_counter() - begin
        kib = self.args.iterations * self.args.batch * (self.args.size // 1024)
        print(f"{elapsed:.6f}\t{kib}")
        return 0

    def run(self) -> int:
        if self.args.workload == "encode":
            return self.encode()
        return self.decode()


def _device_batches() -> int:
    from ..common import device_attribution
    return sum(c["batches"]
               for c in device_attribution.snapshot()["classes"].values())


def main(argv=None) -> int:
    from ..common import device_telemetry
    from ..common.compile_cache import enable_compile_cache
    enable_compile_cache()
    args = build_parser().parse_args(argv)
    before = _device_batches()
    try:
        rc = ErasureCodeBench(args).run()
    except (ValueError, OSError, RuntimeError) as e:
        print(str(e), file=sys.stderr)
        return 1
    # where the timed calls actually ran, observed not predicted: the
    # codec books every device dispatch it fetches (--device-resident
    # never fetches, and is on the device by construction)
    dispatched = _device_batches() - before
    inv = device_telemetry.device_inventory()
    route = "device" if dispatched or args.device_resident else "host"
    print(f"# ec_bench: platform={inv['platform']} "
          f"device_kind={inv['device_kind']} route={route} "
          f"device_dispatches={dispatched}", file=sys.stderr)
    return rc


if __name__ == "__main__":
    sys.exit(main())
