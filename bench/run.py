"""Run one benchmark cell once.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (engine build, program tracing, compile-cache load, warm-up
checks) is timed from process start to the window; the window runs the
cell's traffic for ``--seconds`` and ends with the check then in flight;
then the device's memory peak is read, the engine is freed, the plain
reference runs, and every check is compared with it.  The last stdout
line is the result object; the numbers compared, each with its limit,
are the last lines of stderr.  Exits non-zero, printing no result, when
JAX finds no TPU or fewer chips than the cell asks for.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from collections import Counter  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from typing import Optional  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE = os.path.join(ROOT, ".bench_cache")
TRACE_SECONDS = 15        # a --trace 1 run profiles this much of its window
sys.path.insert(0, BENCH)
sys.path.insert(1, ROOT)          # the program under test
# libtpu logs under /tmp unless told otherwise: keep them in the checkout
os.environ.setdefault("TPU_LOG_DIR", os.path.join(CACHE, "tpu_logs"))

from harness import compare, device, manifest, reference  # noqa: E402
from harness import trace as tr  # noqa: E402


class CompileCounter:
    """Counts JAX's compile events (trace, lowering, backend compile)."""

    def __init__(self):
        self.counts = Counter()

    def __call__(self, name, secs, **_kw):
        if name.startswith("/jax/core/compile/"):
            self.counts[name.rsplit("/", 1)[-1]] += 1

    def total(self) -> int:
        return sum(self.counts.values())


class GcClock:
    """Seconds the interpreter spent in full (generation 2) garbage
    collections: a diagnostic for host stalls inside the window."""

    def __init__(self):
        self.n, self.seconds, self._t = 0, 0.0, None

    def __call__(self, phase, info):
        if info["generation"] != 2:
            return
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.n += 1
            self.seconds += time.perf_counter() - self._t
            self._t = None


@dataclass
class Context:
    """What a metric reader may read (bench/metrics/<name>.py)."""
    records: list                 # the window's checks, in order
    window_s: float
    setup_s: float
    peak_bytes: int
    peaks: dict                   # bench/peaks.json: for roofline readers
    spans: dict = field(default_factory=dict)   # program span totals
    trace: Optional[tr.Summary] = None          # the traced part
    traced_checks: int = 0                      # checks in the traced part


class Tracer:
    """Profiles the first TRACE_SECONDS of the window, in whole checks:
    that bounds the trace's size and the time to read it, whatever the
    cell's check rate."""

    def __init__(self, jax, log_dir: str):
        self.jax, self.dir = jax, log_dir
        self.checks = None
        self.stop_s = 0.0
        self._window = None

    def start(self):
        shutil.rmtree(self.dir, ignore_errors=True)
        opts = self.jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        self.jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._window = self.jax.profiler.TraceAnnotation(tr.WINDOW)
        self._window.__enter__()

    def after_check(self, n: int, elapsed: float):
        if elapsed >= TRACE_SECONDS:
            self.stop(n)

    def stop(self, n: int):
        if self.checks is not None:
            return
        t = time.perf_counter()
        self._window.__exit__(None, None, None)
        self.jax.profiler.stop_trace()
        self.checks, self.stop_s = n, time.perf_counter() - t


def _settings(jax):
    # a fixed path inside the checkout: the path is part of the cache key
    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(CACHE, "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def run(args, fault=None, require=device.require_chips,
        root=manifest.ROOT):
    """One run; returns (result dict, compared).  ``fault``,
    ``require`` and ``root`` exist for the benchmark's own tests."""
    cell = manifest.cell(args.workload, manifest.load(root), root)
    import jax
    _settings(jax)
    devs = require(int(cell.entry["chips"]))
    dev = devs[0]
    peaks = device.peaks(dev.device_kind)
    print(f"device: {dev.platform} {dev.device_kind} x{len(devs)}; "
          f"workload {cell.name}, seed {args.seed} (the seed varies "
          f"nothing here: {cell.traffic['seed']})", flush=True)
    compiles = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(compiles)

    from harness.system import System
    system = System(cell.conf, cell.conf_dir, spans=bool(args.trace))
    if fault is not None:
        fault(system)
    drv = manifest.load_module(manifest.driver_file(
        cell.traffic["driver"]))
    warm = drv.warm(system, cell.traffic, compiles)
    print(f"warm-up: {len(warm)} check(s); compile events "
          f"{dict(compiles.counts)}; capacities {system.capacities()}",
          flush=True)

    tracer = None
    span = contextlib.nullcontext
    if args.trace:
        system.reset_spans()
        tracer = Tracer(jax, os.path.join(CACHE, "trace", cell.name))
        span = jax.profiler.TraceAnnotation
    compiles.counts.clear()
    gc_clock = GcClock()
    gc.callbacks.append(gc_clock)
    setup_s = time.perf_counter() - T0
    if tracer:
        tracer.start()
    records, window_s = drv.window(
        system, cell.traffic, args.seconds, span,
        tracer.after_check if tracer else lambda n, t: None)
    gc.callbacks.remove(gc_clock)
    window_compiles = dict(compiles.counts)
    if tracer:
        tracer.stop(len(records))
    peak = device.memory_peak(devs)
    spans = system.span_totals()
    system.close()
    del system
    gc.collect()
    print(f"window: {len(records)} checks in {window_s:.6f} s; compiles "
          f"inside the window: {sum(window_compiles.values())} "
          f"{window_compiles}; full GCs {gc_clock.n} taking "
          f"{gc_clock.seconds:.6f} s; slowest check "
          f"{max(r.seconds for r in records):.6f} s", flush=True)
    print(f"peak HBM: {peak} bytes = {100 * peak / peaks['hbm_bytes']:.3f}"
          f"% of the chip's {peaks['hbm_bytes']:.0f} ({peaks['name']})",
          flush=True)

    summary = None
    if tracer:
        t = time.perf_counter()
        ev = tr.load(tr.find_xplane(tracer.dir),
                     host_names=set(spans) | {tr.WINDOW, "bench.check"})
        lo, hi = tr.window(ev)
        summary = tr.reduce(ev, lo, hi)
        shutil.rmtree(tracer.dir, ignore_errors=True)
        print(f"trace: first {tracer.checks} checks; busy "
              f"{summary.busy_s:.6f} s of {summary.window_s:.6f} s; device s "
              f"by program {summary.module_s}; idle s by host span "
              f"{summary.idle_by_label}; stop {tracer.stop_s:.3f} s, read "
              f"{time.perf_counter() - t:.3f} s", flush=True)

    so = reference.build(os.path.join(CACHE, "reference"))
    ref = reference.check(so, cell.conf["model"], int(cell.conf["max_depth"]))
    print(f"reference: {ref.distinct} distinct, {ref.generated} generated, "
          f"depth {ref.depth}, violated {ref.violated}, "
          f"{ref.seconds:.3f} s", flush=True)
    checked = warm + records
    compared = compare.compare(checked, ref)
    for rec in records:
        print(f"check: {rec.distinct} distinct, {rec.generated} generated,"
              f" depth {rec.depth}, violated {rec.violated}, "
              f"{rec.seconds:.6f} s")

    ctx = Context(records=records, window_s=window_s,
                  setup_s=setup_s, peak_bytes=peak, peaks=peaks,
                  spans=spans, trace=summary,
                  traced_checks=tracer.checks if tracer else 0)
    metrics = {}
    for m in (cell.per_layer if args.trace else cell.end_to_end):
        v = manifest.load_module(manifest.metric_file(m["name"])).read(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    out_dev = {"platform": dev.platform, "kind": dev.device_kind,
               "count": len(devs), "memory_peak_bytes": peak}
    result = {"correct": compare.ok(compared), "attempted": len(records),
              "failed": compare.failed_checks(records, ref),
              "metrics": metrics, "device": out_dev}
    if summary is not None:
        out_dev["busy_s"] = summary.busy_s
        out_dev["window_s"] = summary.window_s
        result["breakdown"] = {"device_ops": summary.top_ops,
                               "idle_gaps": summary.idle_gaps}
    result["compared"] = compared
    return result, compared


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result, compared = run(args)
    except (device.NoChip, KeyError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    sys.stdout.flush()
    for nm, c in compared.items():
        print(f"{nm} {c['value']} limit {c['limit']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
