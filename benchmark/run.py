#!/usr/bin/env python3
"""Run one cell of the benchmark once, in one process.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is a configuration and a traffic mix named in ``BENCHMARK.json``;
their parameters, and every per-layer metric, sit in files of their own
under ``configs/``, ``traffic/`` and ``metrics/``.  Nothing here names a
cell.  The run makes its data from ``--seed``, warms up every shape the
window uses (set-up), measures for ``--seconds``, drains, compares what
the timed path produced with the plain reference, and prints one JSON
line last on standard output.  It refuses anything but a TPU; ``--rehearsal``
is the same code at the tiny sizes the files give under ``"rehearsal"``,
on whatever JAX finds, prints ``REHEARSAL`` and never the result line.
"""
from __future__ import annotations

import time

_PROCESS_T0 = time.perf_counter()        # set-up is counted from here

import argparse                          # noqa: E402
import gc                                # noqa: E402
import importlib                         # noqa: E402
import json                              # noqa: E402
import os                                # noqa: E402
import resource                          # noqa: E402
import shutil                            # noqa: E402
import sys                               # noqa: E402
from pathlib import Path                 # noqa: E402

REPO = Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmark.lib import allocator, manifest, readers, stats, xplane  # noqa: E402

RUNS_DIR = REPO / ".bench_runs"          # git-ignored; one directory a run

COMPARE = {"<=": lambda v, lim: v <= lim, ">=": lambda v, lim: v >= lim}


class NoAccelerator(RuntimeError):
    pass


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def merged(spec: dict, rehearsal: bool) -> dict:
    """A configuration's or traffic's parameters, with the file's own
    ``rehearsal`` overrides laid over them for a rehearsal."""
    out = {k: v for k, v in spec.items() if k != "rehearsal"}
    if rehearsal:
        for key, value in spec.get("rehearsal", {}).items():
            if isinstance(value, dict) and isinstance(out.get(key), dict):
                out[key] = {**out[key], **value}
            else:
                out[key] = value
    return out


def look_for_chip(chips: int, rehearsal: bool):
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    say(f"device: platform={platform} device_kind={devices[0].device_kind} "
        f"count={len(devices)}")
    if rehearsal:
        return devices
    if platform != "tpu":
        raise NoAccelerator(
            f"jax.devices()[0].platform is {platform!r}, not 'tpu' (a CPU "
            f"rehearsal is --rehearsal, and proves nothing about the chip)")
    if len(devices) < chips:
        raise NoAccelerator(f"the cell asks for {chips} chips, JAX finds "
                            f"{len(devices)}")
    return devices


def host_probe_ms() -> tuple[float, float]:
    """(copy, interpret) milliseconds of two fixed pieces of host work:
    64 MiB of memcpy in 4 MiB pieces, and 200,000 turns of a Python
    loop.  Printed with every run so that a reader can tell a slow host
    (or a slow placement of this process on it) from a slow program."""
    buf = bytearray(4 << 20)
    t0 = time.perf_counter()
    for _ in range(16):
        bytes(buf)
    t1 = time.perf_counter()
    n = 0
    for i in range(200000):
        n += i & 3
    return (t1 - t0) * 1e3, (time.perf_counter() - t1) * 1e3


def end_to_end_value(fn: dict, ops) -> float | None:
    """The arithmetic an end-to-end metric names in the traffic's file."""
    if fn["fn"] == "drained_rate_mib_s":
        return stats.drained_rate_mib_s(ops)
    if fn["fn"] == "latency_percentile_ms":
        lat = stats.latencies_ms(ops)
        say(f"  percentile p{fn['q']}: {len(lat)} samples, "
            f"{stats.samples_beyond(len(lat), fn['q']) if lat else 0} beyond")
        return stats.percentile(lat, fn["q"]) if lat else None
    raise ValueError(f"no end-to-end arithmetic {fn['fn']!r}")


class Profiling:
    """The traced run's profiler stretch: the last ``trace_seconds`` of
    the issuing window.  Spans and counters are read over the stretch
    before it, so the profiler's own cost is in the device numbers'
    stretch and not in theirs.  The capture goes through the program's
    ``ProfilerCapture`` (its one owner of ``jax.profiler``), with the
    Python tracer off: 16 client threads of Python calls would swamp
    the capture."""

    class _Quiet:
        """``jax.profiler`` with the Python tracer switched off."""

        def __init__(self):
            import jax
            self._p = jax.profiler

        def start_trace(self, path):
            opts = self._p.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            self._p.start_trace(path, profiler_options=opts)

        def stop_trace(self):
            self._p.stop_trace()

    # between a clock anchor and the marker op beside it: the device's
    # clock and the host's agree to well under this, so the marker lies
    # inside the stretch the anchors cut (right beside the anchor it fell
    # outside: my chip run, PR 24)
    MARGIN_S = 0.005

    def __init__(self, driver, run_dir: Path):
        import jax
        from ceph_tpu.common.profiler_capture import ProfilerCapture
        self.driver = driver
        self.jax_profiler = jax.profiler
        self.capture = ProfilerCapture(out_dir=run_dir / "profile",
                                       max_captures=2,
                                       profiler=self._Quiet())
        # a small device op (one fusion over 4 MiB, some 10 us) beside
        # each clock anchor: it puts the device's clock next to the
        # host's in the capture, and a cell whose window never touches
        # the chip (a clean read) still has device events to read the
        # idle share from.  A one-element add left no event on the ops
        # line (my chip run, PR 24).  Compiled here, in set-up
        import jax.numpy as jnp
        marker = jax.jit(lambda x: x * 2.0 + 1.0)
        field = jnp.zeros((1024, 1024), jnp.float32)
        jax.block_until_ready(marker(field))
        self.marker = lambda: jax.block_until_ready(marker(field))
        self.before = self.at_start = None
        self.t_start = self.t_stop = None
        self.spans = []
        self.error = None

    def start(self) -> None:
        self.at_start = self.driver.snapshot()
        got = self.capture.start("bench")
        if "error" in got:
            self.error = got["error"]
            return
        self.path = got["path"]
        self.t_start = xplane.clock_anchor(self.jax_profiler)
        time.sleep(self.MARGIN_S)
        self.marker()

    def stop(self) -> None:
        if self.error is not None or self.t_start is None:
            return
        self.marker()
        time.sleep(self.MARGIN_S)
        self.t_stop = xplane.clock_anchor(self.jax_profiler)
        self.spans = self.driver.host_spans()
        got = self.capture.stop()
        if "error" in got:
            self.error = got["error"]

    def reduce(self) -> dict | None:
        """The capture, cut to the stretch between the two anchors and
        reduced; None (with the reason said) where there is none."""
        if self.error is not None or self.t_stop is None:
            say(f"  profile: no capture ({self.error})")
            return None
        cap = xplane.load(self.path)
        say(f"  profile: {cap['file']} planes={cap['lines']}")
        if len(cap["anchors"]) < 2:
            say("  profile: clock anchors not found in the capture")
            return None
        lo, hi = cap["anchors"][0], cap["anchors"][-1]
        for plane, evs in cap["devices"].items():
            if evs:
                say(f"  profile: {plane}: {len(evs)} ops from "
                    f"{min(e[1] for e in evs) / 1e9:.6f} s to "
                    f"{max(e[1] + e[2] for e in evs) / 1e9:.6f} s; anchors "
                    f"at {lo / 1e9:.6f} s and {hi / 1e9:.6f} s")
        red = xplane.reduce(cap, lo, hi)
        if red["devices"] == 0:
            say("  profile: the capture has no device plane")
            return None
        # host spans onto the capture's clock, through the first anchor
        spans = [(n, lo + (a - self.t_start) * 1e9, lo + (b - self.t_start) * 1e9)
                 for n, a, b in self.spans]
        red["idle_gaps"] = xplane.attribute_gaps(red["gaps"], spans)
        return red


def delta(after: dict, before: dict) -> dict:
    """after - before of a driver's snapshot."""
    def pairs(a, b):
        return {k: (v[0] - b.get(k, (0.0, 0))[0], v[1] - b.get(k, (0.0, 0))[1])
                for k, v in a.items()}
    counters = {
        name: {k: v - before["counters"].get(name, {}).get(k, 0)
               for k, v in vals.items()}
        for name, vals in after["counters"].items()}
    return {"spans": pairs(after["spans"], before["spans"]),
            "rpc": pairs(after["rpc"], before["rpc"]), "counters": counters}


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             rehearsal: bool = False, control: str | None = None,
             repo: Path = REPO) -> dict:
    """Everything but argument parsing and the last line: returns the
    result object.  Raises :class:`NoAccelerator` where the chip the
    cell needs is not there."""
    say(f"allocator thresholds pinned: {allocator.pin()}")
    cell = manifest.load_cell(workload, repo)
    config = merged(cell["config"], rehearsal)
    traffic = merged(cell["traffic"], rehearsal)

    from ceph_tpu.common.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    devices = look_for_chip(cell["chips"], rehearsal)
    from benchmark.lib.compile_meter import CompileMeter
    meter = CompileMeter()
    say(f"cell: {workload} = {cell['config_name']} x {cell['traffic_name']} "
        f"seed={seed} seconds={seconds} trace={int(trace)} "
        f"control={control} compile_cache={cache_dir}")

    RUNS_DIR.mkdir(exist_ok=True)
    run_dir = RUNS_DIR / f"run-{os.getpid()}"
    shutil.rmtree(run_dir, ignore_errors=True)
    run_dir.mkdir()
    driver_mod = importlib.import_module(
        f"benchmark.drivers.{config['driver']}")
    driver = driver_mod.Driver(config, traffic, seed, run_dir, control)
    try:
        driver.setup()
        prof = Profiling(driver, run_dir) if trace else None
        schedule = []
        if prof is not None:
            stretch = min(float(traffic.get("trace_seconds", 5.0)),
                          seconds / 2.0)
            schedule = [(seconds - stretch, prof.start), (seconds, prof.stop)]
            prof.before = driver.snapshot()
        gc.collect()
        gc.freeze()
        say("host probe before the window: copy %.2f ms, interpret %.2f ms"
            % host_probe_ms())
        built_before = meter.executables
        usage_before = resource.getrusage(resource.RUSAGE_SELF)
        setup_s = time.perf_counter() - _PROCESS_T0
        ops, errors, t_start = driver.window(seconds, schedule)
        built_in_window = meter.executables - built_before
        usage = resource.getrusage(resource.RUSAGE_SELF)
        gc.unfreeze()
        say("host probe after the window: copy %.2f ms, interpret %.2f ms"
            % host_probe_ms())
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices[:cell["chips"]])

        first, last = stats.drained_span(ops)
        attempted = len(ops)
        failed = sum(1 for o in ops if not o.ok)
        say(f"window: {attempted} ops attempted, {failed} failed, issued "
            f"for {seconds} s, drained span {last - first:.3f} s "
            f"(drain {max(0.0, last - t_start - seconds):.3f} s)")
        for err in errors:
            say(f"  failed: {err}")
        # acks per tenth of the span: a stall shows as a thin bucket
        tenth = (last - first) / 10.0 or 1.0
        buckets = [0] * 10
        for o in ops:
            if o.ok:
                buckets[min(9, int((o.t1 - first) / tenth))] += 1
        say(f"  acked per tenth of the span: {buckets}")
        # what this process (the system under test; the rados clients
        # are another) took of the host meanwhile: a run that is slow on
        # the same CPU seconds waited, one that took more ran slower
        say(f"  this process over the window: user "
            f"{usage.ru_utime - usage_before.ru_utime:.2f} s, system "
            f"{usage.ru_stime - usage_before.ru_stime:.2f} s, switches "
            f"voluntary {usage.ru_nvcsw - usage_before.ru_nvcsw} "
            f"involuntary {usage.ru_nivcsw - usage_before.ru_nivcsw}")

        metrics: dict = {}
        if not trace:
            for m in cell["end_to_end"]:
                if m["name"] == "setup_s":
                    value = setup_s
                else:
                    fn = traffic["end_to_end"].get(m["name"])
                    if fn is None:
                        raise ValueError(
                            f"traffic {cell['traffic_name']!r} does not say "
                            f"how {m['name']!r} is reckoned")
                    value = end_to_end_value(fn, ops)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device = {"platform": devices[0].platform,
                  "kind": devices[0].device_kind, "count": len(devices),
                  "memory_peak_bytes": int(peak)}
        breakdown = None
        if trace:
            counted = delta(prof.at_start or driver.snapshot(), prof.before)
            red = prof.reduce()
            t_a = prof.t_start if prof.t_start is not None else float("inf")
            t_b = prof.t_stop if prof.t_stop is not None else float("inf")
            ctx = {"ops": ops, **counted,
                   "counted_ops": sum(1 for o in ops if o.ok and o.t1 <= t_a),
                   "device": red,
                   "traced_ops": sum(1 for o in ops
                                     if o.ok and t_a <= o.t1 <= t_b),
                   "device_kind": devices[0].device_kind, "config": config}
            if ctx["counted_ops"]:
                per_op = sorted(((s / ctx["counted_ops"] * 1e3, n)
                                 for n, (s, _c) in ctx["spans"].items()),
                                reverse=True)[:16]
                say("  span ms per op: " + ", ".join(
                    f"{n}={ms:.2f}" for ms, n in per_op))
            for spec in cell["per_layer"]:
                value = readers.read_metric(spec, ctx)
                if value is not None:
                    metrics[spec["name"]] = {"value": value,
                                             "unit": spec["unit"]}
            if red is not None:
                device["busy_s"] = red["busy_s"]
                device["window_s"] = red["window_s"]
                breakdown = {"device_ops": red["device_ops"],
                             "idle_gaps": red["idle_gaps"]}

        numbers = {"compiles_in_window": (built_in_window, "<=", 0),
                   "ops_failed": (failed, "<=", 0),
                   "ops_attempted": (attempted, ">=", 1)}
        t_check = time.perf_counter()
        numbers.update(driver.after_window())
        say(f"comparison: {time.perf_counter() - t_check:.2f} s after the "
            f"window (not in setup_s)")
    finally:
        driver.close()
        shutil.rmtree(run_dir, ignore_errors=True)

    checks = {}
    correct = True
    for name, (value, op, limit) in numbers.items():
        ok = COMPARE[op](value, limit)
        correct = correct and ok
        checks[name] = {"value": value, "limit": f"{op}{limit}", "ok": ok}
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    say(f"set-up {setup_s:.2f} s, executables built in the window: "
        f"{built_in_window}")
    for name, c in checks.items():
        say(f"check {name}: {c['value']} (limit {c['limit']}) "
            f"{'ok' if c['ok'] else 'NOT OK'}")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny sizes on whatever platform JAX finds; prints "
                         "REHEARSAL and never the result line")
    ap.add_argument("--control", default=None,
                    help="put the cell's control in the program's place "
                         "(the builder's and the tests' proof that "
                         "`correct` can come out false); never the "
                         "driver's command")
    args = ap.parse_args(argv)
    seconds = args.seconds
    if seconds is None:
        seconds = float(manifest.load_benchmark()["run_seconds"])
    if args.rehearsal:
        print("REHEARSAL (tiny sizes; proves nothing about the chip)",
              flush=True)
    try:
        result = run_cell(args.workload, args.seed, seconds, bool(args.trace),
                          rehearsal=args.rehearsal, control=args.control)
    except NoAccelerator as e:
        say(f"benchmark: no accelerator: {e}")
        return 1
    except manifest.ManifestError as e:
        say(f"benchmark: {e}")
        return 2
    line = json.dumps(result)
    if args.rehearsal:
        print("REHEARSAL " + line, flush=True)
    else:
        print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
