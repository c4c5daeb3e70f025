"""One run of one benchmark cell on the chip.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``workloads`` in BENCHMARK.json) names a configuration
(``benchmark/configs/<config>.json``, which names its driver,
``benchmark/drivers/<driver>.py``) and a traffic mix
(``benchmark/traffic/<mix>.json``).  The driver loads and warms up the
cell's own shapes, measures for ``--seconds``, then checks what the
timed path produced against the plain reference.  Each metric is read
by ``benchmark/metrics/<metric>.py``: with ``--trace 0`` the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics, from a
profiler trace of the window.

The last line of stdout is one JSON object (correct, attempted, failed,
metrics, device, breakdown with --trace 1, checks).  The numbers
compared, each beside its limit, are also the last lines of stderr.
Without a TPU, or with fewer chips than the cell asks for, it exits 2
and prints no result."""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time


def _process_start() -> float:
    """Wall-clock time this process started (from /proc), else now."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/stat") as f:
            btime = next(int(line.split()[1]) for line in f
                         if line.startswith("btime"))
        return btime + ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, StopIteration, IndexError):
        return time.time()


PROCESS_START = _process_start()

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
# the compile cache lives at one fixed path inside the checkout (listed in
# .gitignore); set before JAX is imported, and the program's own cache
# choice (ops/aot.configure) reads the same variable
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
# no eviction: its access-time files raced under concurrent compiles on
# the chip machine and entries went unwritten (my chip run, PR 22)
os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"

from benchmark import common  # noqa: E402


#: seconds of the window the profiler traces with --trace 1: the
#: profiler keeps some 6.29 million device events and drops the rest,
#: and a replay step at N = 4 runs 1.4 million a second (my chip run,
#: PR 22), so a longer trace would read the device as idle
TRACE_S = 2.0


class NoChip(Exception):
    """No TPU, or fewer chips than the cell asks for."""


class Context:
    """What a driver gets: the cell's files, the run's arguments, the
    window's clock and, with --trace 1, the profiler around it."""

    def __init__(self, config: dict, traffic: dict, seed: int,
                 seconds: float, trace: bool):
        self.config = config
        self.traffic = traffic
        self.seed = seed
        self.seconds = seconds
        self.trace = trace
        self.setup_s = None
        self.window_t0 = None
        self.window_s = None
        self.memory_peak_bytes = None
        self.trace_dir = None
        self.trace_s = None
        self._tracing = False

    @staticmethod
    def span(name: str):
        """A host span of the benchmark's own call into the program (on
        the device trace's clock when a trace runs)."""
        from jax.profiler import TraceAnnotation

        return TraceAnnotation(name)

    def begin_window(self) -> None:
        """The first measured instant: set-up ends here."""
        import jax

        self.setup_s = time.time() - PROCESS_START
        if self.trace:
            self.trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            self._tracing = True
        self.window_t0 = time.perf_counter()

    def trace_due(self) -> bool:
        """True once the traced part (the window's first TRACE_S
        seconds) is over."""
        return (self._tracing and time.perf_counter() - self.window_t0
                >= TRACE_S)

    def stop_trace(self) -> None:
        import jax

        if self._tracing:
            # the traced part ends here; writing the trace out takes
            # seconds more
            self.trace_s = time.perf_counter() - self.window_t0
            jax.profiler.stop_trace()
            self._tracing = False

    def end_window(self) -> None:
        self.window_s = time.perf_counter() - self.window_t0
        self.stop_trace()

    def read_memory(self) -> None:
        """Peak device memory on the fullest chip; read once the window
        has closed and before the reference runs."""
        import jax

        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in jax.devices()]
        self.memory_peak_bytes = int(max(peaks))


def _tpu_devices(chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found {len(devs)}")
    return devs


def _metric_names(spec: dict, cell: str, trace: bool) -> list:
    reported = [m["name"] for m in spec["end_to_end"]
                if cell in m.get("workloads", [cell])]
    if not trace:
        return reported
    return [m["name"] for m in spec["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in reported)]


def _units(spec: dict) -> dict:
    return {m["name"]: m["unit"] for m in spec["end_to_end"] + spec["per_layer"]}


def run_cell(workload: str, seed: int, seconds: float, trace: bool) -> dict:
    """Run one cell and return its result object."""
    import jax

    spec = common.load_json(common.SPEC_FILE)
    cell = next((w for w in spec["workloads"] if w["name"] == workload), None)
    if cell is None:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
    _tpu_devices(cell["chips"])
    # every program the window runs goes to the persistent cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    here = common.HERE
    config = common.load_json(os.path.join(here, "configs",
                                           cell["config"] + ".json"))
    traffic = common.load_json(os.path.join(here, "traffic",
                                            cell["traffic"] + ".json"))
    driver = common.import_file(
        os.path.join(here, "drivers", config["driver"] + ".py"),
        "bench_driver_" + config["driver"])
    ctx = Context(config, traffic, seed, seconds, trace)
    try:
        out = driver.run(ctx)
        readings = dict(out["readings"])
        readings.update(setup_s=ctx.setup_s, window_s=ctx.window_s)
        breakdown = None
        if trace:
            from benchmark import trace as trace_mod

            red = trace_mod.reduce(ctx.trace_dir, ctx.trace_s)
            readings["trace"] = red
            print(f"bench: traced {red['window_s']:.3f} s, busy "
                  f"{red['busy_s']:.6f} s, operations alone "
                  f"{red['ops_busy_s']:.6f} s in {red['op_events']} "
                  f"events", file=sys.stderr)
            breakdown = {"device_ops": red["top_ops"],
                         "idle_gaps": red["top_idle"]}
    finally:
        if ctx.trace_dir:
            shutil.rmtree(ctx.trace_dir, ignore_errors=True)

    units = _units(spec)
    metrics = {}
    for name in _metric_names(spec, workload, trace):
        reader = common.import_file(
            os.path.join(here, "metrics", name + ".py"),
            "bench_metric_" + name.replace(".", "_"))
        value = reader.read(readings)
        if value is not None:
            metrics[name] = {"value": float(value), "unit": units[name]}

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": jax.device_count(),
              "memory_peak_bytes": ctx.memory_peak_bytes}
    if trace:
        device["busy_s"] = readings["trace"]["busy_s"]
        device["window_s"] = readings["trace"]["window_s"]
    checks = {k: {"value": v, "limit": lim}
              for k, (v, lim) in out["checks"].items()}
    result = {
        "correct": all(c["value"] <= c["limit"] for c in checks.values()),
        "attempted": out["attempted"],
        "failed": out["failed"],
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    result["_internals"] = out.get("internals")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except NoChip as exc:
        print(f"benchmark: {exc}; nothing was run", file=sys.stderr)
        return 2
    result.pop("_internals", None)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
