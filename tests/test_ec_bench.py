"""``ceph_tpu.bench.ec_bench``: the metric of record's own command.

Driven in-process exactly as ``chip_smoke.py`` ``phase_ec_bench`` and
upstream's ``qa/workunits/erasure-code/bench.sh`` drive
``ceph_erasure_code_benchmark``: every plugin x technique the registry
ships, each with a profile its plugin documents, through ``encode``,
``decode --erasures 1``, ``decode --erasures e`` (e the most the profile
always recovers) and ``decode --erasures e -E exhaustive``; then
bench.sh's own k/m grid, and the flags one at a time.

What every case holds: rc 0, one output line ``<seconds>\\t<KiB>`` with
KiB = iterations x size / 1024 (what bench.sh divides to get MiB/s),
the stderr line that says where the calls ran, and — through a spy on
the plugin instance the command builds — that each decode was handed
exactly the survivors the flags ask for and recovered chunks equal to
the ones encode produced.  CPU, sizes <= 64 KiB: counts and bytes, never
a rate.
"""
from __future__ import annotations

import math
import random
import re

import numpy as np
import pytest

from ceph_tpu.bench import ec_bench

SIZE = 16384
ITERATIONS = 3
ROUTE_LINE = re.compile(
    r"^# ec_bench: platform=\S+ device_kind=.* route=(host|device) "
    r"device_dispatches=(\d+)$")

# (plugin, profile, e): e is the most erasures the profile ALWAYS
# recovers (m for an MDS code, c for shec, 1 for lrc's k=4 m=2 l=3 whose
# two losses in one local group plus its global share cannot be read)
PLUGINS = [
    ("jax_rs", {"k": 4, "m": 2, "technique": "reed_sol_van",
                "device": "numpy"}, 2),
    ("jax_rs", {"k": 4, "m": 2, "technique": "cauchy",
                "device": "numpy"}, 2),
    ("jax_rs", {"k": 4, "m": 2, "technique": "vandermonde",
                "device": "numpy"}, 2),
    ("jerasure", {"k": 4, "m": 2, "technique": "reed_sol_van"}, 2),
    ("jerasure", {"k": 4, "m": 2, "technique": "reed_sol_r6_op"}, 2),
    ("jerasure", {"k": 4, "m": 2, "technique": "cauchy_orig",
                  "packetsize": 32}, 2),
    ("jerasure", {"k": 4, "m": 2, "technique": "cauchy_good",
                  "packetsize": 32}, 2),
    ("jerasure", {"k": 4, "m": 2, "technique": "liberation", "w": 7,
                  "packetsize": 32}, 2),
    ("jerasure", {"k": 4, "m": 2, "technique": "blaum_roth", "w": 6,
                  "packetsize": 32}, 2),
    ("jerasure", {"k": 4, "m": 2, "technique": "liber8tion",
                  "packetsize": 32}, 2),
    ("jerasure", {"k": 4, "m": 2, "technique": "reed_sol_van",
                  "w": 16}, 2),
    ("isa", {"k": 4, "m": 2, "technique": "reed_sol_van"}, 2),
    ("isa", {"k": 4, "m": 2, "technique": "cauchy"}, 2),
    ("shec", {"k": 4, "m": 3, "c": 2, "technique": "multiple"}, 2),
    ("shec", {"k": 4, "m": 3, "c": 2, "technique": "single"}, 2),
    ("lrc", {"k": 4, "m": 2, "l": 3}, 1),
    ("clay", {"k": 4, "m": 2, "d": 5}, 2),
    ("clay", {"k": 4, "m": 2, "d": 5, "scalar_mds": "isa"}, 2),
    ("pm_regen", {"k": 3, "m": 2, "mode": "mbr"}, 2),
    ("pm_regen", {"k": 3, "m": 3, "mode": "msr"}, 3),
    ("xor", {"k": 3, "m": 1}, 1),
    ("cpp_rs", {"k": 4, "m": 2, "technique": "reed_sol_van"}, 2),
    ("cpp_rs", {"k": 4, "m": 2, "technique": "cauchy"}, 2),
    ("cpp_rs", {"k": 4, "m": 2, "technique": "vandermonde_isa"}, 2),
]


def _cases():
    for plugin, profile, e in PLUGINS:
        rid = "-".join([plugin, *(f"{k}={v}" for k, v in profile.items()
                                  if k != "device")])
        yield pytest.param(plugin, profile, "encode", 0, id=f"{rid}-encode")
        for n_erased in sorted({1, e}):
            yield pytest.param(plugin, profile, "random", n_erased,
                               id=f"{rid}-decode-e{n_erased}")
        yield pytest.param(plugin, profile, "exhaustive", e,
                           id=f"{rid}-exhaustive-e{e}")


def _argv(plugin: str, profile: dict, *rest: str, size: int = SIZE,
          iterations: int = ITERATIONS) -> list[str]:
    argv = ["--plugin", plugin, "--size", str(size),
            "--iterations", str(iterations)]
    for k, v in profile.items():
        argv += ["--parameter", f"{k}={v}"]
    return argv + list(rest)


class _Spy:
    """The plugin instance ``ec_bench`` built, with every encode's
    chunks and every decode's survivors and answer kept."""

    def __init__(self, ec):
        self._ec = ec
        self.encoded: dict[int, np.ndarray] = {}
        self.decodes: list[tuple[set, set, dict]] = []

    def __getattr__(self, name):
        return getattr(self._ec, name)

    def encode(self, want, data):
        out = self._ec.encode(want, data)
        self.encoded = {i: np.array(c, copy=True) for i, c in out.items()}
        return out

    def decode(self, want, chunks, chunk_size):
        out = self._ec.decode(want, chunks, chunk_size)
        self.decodes.append((set(want), set(chunks), out))
        return out

    def assert_recovered(self) -> None:
        for want, _have, out in self.decodes:
            for i in want:
                assert np.array_equal(out[i], self.encoded[i]), \
                    f"chunk {i} decoded differently from what was encoded"


@pytest.fixture
def spies(monkeypatch):
    """Every plugin instance the command builds, wrapped in a spy."""
    built: list[_Spy] = []
    real = ec_bench.ErasureCodeBench._factory

    def factory(self):
        built.append(_Spy(real(self)))
        return built[-1]
    monkeypatch.setattr(ec_bench.ErasureCodeBench, "_factory", factory)
    return built


def _run(capsys, argv: list[str]) -> tuple[int, str, str]:
    random.seed(0x5EED)
    rc = ec_bench.main(argv)
    cap = capsys.readouterr()
    return rc, cap.out, cap.err


def _assert_output(out: str, err: str, kib: int) -> str:
    """The upstream contract: ONE line, seconds TAB KiB; then our own
    stderr line.  Returns the route."""
    assert re.fullmatch(r"\d+\.\d{6}\t\d+\n", out), repr(out)
    secs, got_kib = out.split()
    assert float(secs) >= 0.0 and int(got_kib) == kib
    m = ROUTE_LINE.match(err.strip().splitlines()[-1])
    assert m, err
    return m.group(1)


@pytest.mark.parametrize("plugin, profile, workload, n_erased", _cases())
def test_every_registered_plugin(plugin, profile, workload, n_erased,
                                 spies, capsys):
    rest = {"encode": ["--workload", "encode"],
            "random": ["--workload", "decode",
                       "--erasures", str(n_erased)],
            "exhaustive": ["--workload", "decode", "--erasures",
                           str(n_erased), "-E", "exhaustive"]}[workload]
    iterations = 1 if workload == "exhaustive" else ITERATIONS
    rc, out, err = _run(capsys, _argv(plugin, profile, *rest,
                                      iterations=iterations))
    assert rc == 0, err
    route = _assert_output(out, err, iterations * SIZE // 1024)
    if profile.get("device") == "numpy":
        assert route == "host"
    spy, = spies
    n = spy.get_chunk_count()
    assert set(spy.encoded) == set(range(n))
    if workload == "encode":
        assert not spy.decodes
        return
    # each decode saw exactly n - e survivors, all of them real chunks
    assert all(len(have) == n - n_erased and have <= set(range(n))
               for _want, have, _out in spy.decodes)
    if workload == "random":
        assert len(spy.decodes) == ITERATIONS
    else:
        # -E exhaustive: every C(n, e) pattern once, none twice
        patterns = [frozenset(have) for _w, have, _o in spy.decodes]
        assert len(patterns) == len(set(patterns)) == math.comb(n, n_erased)
    spy.assert_recovered()


# bench.sh:49-56: "for k in 2 3 4 6 10; for m in 1 2 3 4", k >= m
BENCH_SH_GRID = [(k, m) for k in (2, 3, 4, 6, 10) for m in (1, 2, 3, 4)
                 if k >= m]


@pytest.mark.parametrize("workload", ["encode", "decode"])
@pytest.mark.parametrize("k, m", BENCH_SH_GRID,
                         ids=[f"k{k}m{m}" for k, m in BENCH_SH_GRID])
def test_bench_sh_grid(k, m, workload, spies, capsys):
    """Upstream's sweep on the deployment's own technique: shapes that
    do not divide the way (8, 4) does (k = 3, 6, 10), decode at the
    profile's limit (``--erasures m``)."""
    profile = {"k": k, "m": m, "technique": "cauchy", "device": "numpy"}
    size = 4096 * k          # bench.sh's SIZE=4096 a chunk
    rest = ["--workload", workload] + \
        (["--erasures", str(m)] if workload == "decode" else [])
    rc, out, err = _run(capsys, _argv("jax_rs", profile, *rest, size=size))
    assert rc == 0, err
    assert _assert_output(out, err, ITERATIONS * size // 1024) == "host"
    spy, = spies
    assert len(spy.encoded) == k + m
    assert all(c.nbytes == 4096 for c in spy.encoded.values())
    if workload == "decode":
        assert [len(have) for _w, have, _o in spy.decodes] == [k] * ITERATIONS
        spy.assert_recovered()


JAX_RS = ("jax_rs", {"k": 4, "m": 2, "technique": "cauchy",
                     "device": "numpy"})


def test_erased_given_twice_names_the_pattern(spies, capsys):
    rc, out, err = _run(capsys, _argv(
        *JAX_RS, "--workload", "decode", "--erased", "0", "--erased", "5"))
    assert rc == 0, err
    _assert_output(out, err, ITERATIONS * SIZE // 1024)
    spy, = spies
    assert [have for _w, have, _o in spy.decodes] == \
        [{1, 2, 3, 4}] * ITERATIONS
    spy.assert_recovered()


def test_batch_encode_equals_single_calls(spies, capsys, monkeypatch):
    """``--batch 4`` folds four stripes into one codec call: KiB counts
    all four, and the parity is the single call's, four times over."""
    from ceph_tpu.ops.codec import RSCodec
    calls: list[tuple[np.ndarray, np.ndarray]] = []
    real = RSCodec.encode

    def encode(self, data):
        out = real(self, data)
        calls.append((np.array(data, copy=True), np.array(out, copy=True)))
        return out
    monkeypatch.setattr(RSCodec, "encode", encode)
    rc, out, err = _run(capsys, _argv(
        *JAX_RS, "--workload", "encode", "--batch", "4"))
    assert rc == 0, err
    _assert_output(out, err, ITERATIONS * 4 * SIZE // 1024)
    spy, = spies
    single = spy.encode(set(range(6)), b"X" * SIZE)
    chunk = SIZE // 4
    batched = [(d, p) for d, p in calls if d.shape == (4, 4 * chunk)]
    assert len(batched) == 1 + ITERATIONS           # warm-up + timed
    for data, parity in batched:
        assert parity.shape == (2, 4 * chunk)
        for s in range(4):
            sl = slice(s * chunk, (s + 1) * chunk)
            for i in range(4):
                assert np.array_equal(data[i, sl], single[i])
            for j in range(2):
                assert np.array_equal(parity[j, sl], single[4 + j])


def test_batch_decode_equals_single_calls(spies, capsys, monkeypatch):
    from ceph_tpu.ops.codec import RSCodec
    answers: list[np.ndarray] = []
    real = RSCodec.decode_batch

    def decode_batch(self, stack, src, erasures):
        assert list(src) == [1, 2, 3, 4] and list(erasures) == [0, 5]
        answers.append(np.array(real(self, stack, src, erasures)))
        return answers[-1]
    monkeypatch.setattr(RSCodec, "decode_batch", decode_batch)
    rc, out, err = _run(capsys, _argv(
        *JAX_RS, "--workload", "decode", "--batch", "4",
        "--erased", "0", "--erased", "5"))
    assert rc == 0, err
    _assert_output(out, err, ITERATIONS * 4 * SIZE // 1024)
    spy, = spies
    assert len(answers) == 1 + ITERATIONS
    for got in answers:
        assert got.shape == (4, 2, SIZE // 4)
        for s in range(4):
            assert np.array_equal(got[s, 0], spy.encoded[0])
            assert np.array_equal(got[s, 1], spy.encoded[5])


@pytest.mark.parametrize("argv, message", [
    (["--plugin", "jax_rs", "-P", "k=four"], "invalid literal"),
    (["--plugin", "jax_rs", "-P", "technique=nope", "-P", "device=numpy"],
     "technique=nope must be one of"),
    (["--plugin", "no_such_plugin"], "no_such_plugin"),
    (["--plugin", "jax_rs", "-P", "k=4", "-P", "m=2", "-P", "device=numpy",
      "--workload", "decode", "--erasures", "3"],
     "need 4 chunks, only 3 available"),
    (["--plugin", "xor", "-P", "k=3", "--workload", "decode",
      "--erasures", "2"], "xor cannot recover 2 erasures"),
], ids=["bad-int", "bad-technique", "unknown-plugin", "too-many-erasures",
        "too-many-erasures-errno"])
def test_refusals_exit_nonzero_with_a_message(argv, message, capsys):
    rc, out, err = _run(capsys, argv + ["--size", "4096"])
    assert rc != 0
    assert out == ""                 # no result line for a refused run
    assert message in err


def test_parameter_without_equals_is_ignored_and_said(capsys):
    """Upstream's wording: the parameter is skipped, the run goes on
    with the plugin's default for it."""
    rc, out, err = _run(capsys, [
        "--plugin", "jax_rs", "-P", "k=4", "-P", "m=2", "-P", "bogus",
        "-P", "device=numpy", "--size", "4096"])
    assert rc == 0
    assert "--parameter bogus ignored because it does not contain " \
           "exactly one =" in err
    _assert_output(out, err, 4)
