"""Benchmark of the max-flow system on a TPU: one cell per call.

    python3 bench/run.py --workload rlg.cold --seed 7 --seconds 51 --trace 0

Everything is found by name from ``BENCHMARK.json`` at the checkout root:
the cell names a configuration (``bench/configs/<config>.json``) and a
traffic mix (``bench/traffic/<traffic>.json``); the mix names the driver
that runs it (``bench/drivers/<driver>.py``: set-up, measured window,
comparison with the reference); each per-layer metric has its reader
(``bench/metrics/<metric>.py``).  A new cell, configuration, mix or
metric is new files and new entries, with no edit here.

A run: generate the instances from ``--seed``, warm every shape the cell
uses (that and process start-up are ``setup_s``), measure for
``--seconds``, read the peak device memory, then compare every answer of
the window with the plain reference (``bench/reference.py``).  With
``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` the driver runs its traced part under the profiler and the
result carries the per-layer metrics, the device's busy and traced
seconds and a breakdown.  The last line of standard output is the result
as one JSON object; the numbers compared with the reference, each beside
its limit, are the last lines of standard error.

The run exits non-zero with no result when JAX finds no TPU, fewer chips
than the cell asks for, or no program next to the benchmark.  The
persistent compilation cache is ``<checkout>/.jax_cache`` (handed to the
program through ``$JAX_COMPILATION_CACHE_DIR``) and keeps every program,
so only the first run of a cell in a checkout compiles.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()  # setup_s counts from here

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))


class BenchError(SystemExit):
    """A run that must end without a result (exit code 2)."""

    def __init__(self, msg: str):
        print(f"bench: {msg}", file=sys.stderr, flush=True)
        super().__init__(2)


def load_module(path: Path, name: str):
    """Import a benchmark file by its path (metric files carry dots)."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.is_file():
        raise BenchError(f"missing benchmark file {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(workload: str, root: Path = ROOT) -> dict:
    """The cell's entry, its configuration, its traffic mix and the
    metrics that apply to it, all found by name."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise BenchError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    config = json.loads((root / configs[cell["config"]]["file"]).read_text())
    traffic = json.loads(
        (BENCH / "traffic" / f"{cell['traffic']}.json").read_text())

    def applies(m):
        return workload in m.get("workloads", [workload])

    e2e = [m for m in spec["end_to_end"] if applies(m)]
    layer = [m for m in spec["per_layer"] if applies(m)]
    return {"cell": cell, "config": config, "traffic": traffic,
            "end_to_end": e2e, "per_layer": layer}


class CompileClock:
    """Backend compilations (XLA and Mosaic compiling a lowered program,
    or reading it from the persistent cache), counted and summed from
    JAX's monitoring events."""

    def __init__(self):
        import jax

        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += duration


class Run:
    """What one run hands its driver and its metric readers: the seed,
    the configuration and mix, host spans, counts, and after a traced
    part the trace's reduction."""

    def __init__(self, workload: str, seed: int, loaded: dict,
                 tracing: bool):
        self.workload = workload
        self.seed = seed
        self.config = loaded["config"]
        self.traffic = loaded["traffic"]
        self.tracing = tracing
        self.spans: dict[str, list[float]] = {}
        self.counts: dict[str, list[float]] = {}
        self.seconds = 0.0  # the measured window's length
        self.trace = None  # trace_reduce.Reduction after a traced part
        self.peak_bytes: int | None = None
        self.driver_state: dict = {}  # what the driver's set-up returned
        self.device_kind: str | None = None

    @contextlib.contextmanager
    def span(self, name: str):
        """A host span of the benchmark's own, on ``perf_counter``; in a
        traced part also a ``TraceAnnotation`` named ``bench.<name>`` on
        the profiler's clock, to which idle gaps are attributed."""
        ann = contextlib.nullcontext()
        if self.tracing:
            import jax

            ann = jax.profiler.TraceAnnotation(f"bench.{name}")
        t0 = time.perf_counter()
        with ann:
            yield
        self.spans.setdefault(name, []).append(time.perf_counter() - t0)

    def count(self, name: str, value: float) -> None:
        self.counts.setdefault(name, []).append(value)


def require_chip(chips: int) -> dict:
    """The device section of the result, or no result at all when the
    backend is not a TPU or holds fewer chips than the cell asks for."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"no TPU (backend {devs[0].platform!r}); nothing "
                         "was measured")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, {len(devs)} "
                         "visible")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peak_bytes() -> int | None:
    import jax

    stats = [d.memory_stats() for d in jax.local_devices()]
    vals = [int(s["peak_bytes_in_use"]) for s in stats
            if s and "peak_bytes_in_use" in s]
    return max(vals) if vals else None


def _traced(run: Run, fn):
    """Run ``fn`` under the profiler and reduce its trace."""
    import jax

    import trace_reduce

    with tempfile.TemporaryDirectory(prefix="bench_trace_") as d:
        jax.profiler.start_trace(d)
        try:
            with run.span("traced"):
                out = fn()
        finally:
            jax.profiler.stop_trace()
        run.trace = trace_reduce.reduce_dir(d, window="bench.traced")
    return out


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             root: Path = ROOT, chip: bool = True,
             config_overrides: dict | None = None) -> dict:
    """One run of a cell; returns the result object.  ``chip=False`` and
    the overrides exist for the CPU rehearsals under ``bench/tests``,
    which skip the look for a chip and shrink sizes."""
    loaded = load_cell(workload, root)
    if not (root / "src" / "repro").is_dir():
        raise BenchError("no program next to the benchmark (src/repro)")
    sys.path.insert(0, str(root / "src"))
    import jax
    from repro.runtime.cache import ENV_VAR, enable_compile_cache

    # the cache lives in the checkout whatever the environment says, and
    # keeps every program, however fast it compiled
    os.environ[ENV_VAR] = str(root / ".jax_cache")
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    device = require_chip(int(loaded["cell"]["chips"])) if chip else {
        "platform": "cpu", "kind": "cpu", "count": 1}
    loaded["config"].update(config_overrides or {})
    run = Run(workload, seed, loaded, tracing=trace)
    run.seconds = seconds
    run.device_kind = device["kind"]
    driver = load_module(BENCH / "drivers" / f"{run.traffic['driver']}.py",
                         f"bench_driver_{run.traffic['driver']}")
    clock = CompileClock()
    state = driver.setup(run)
    run.driver_state = state
    t_window = time.perf_counter()
    setup_s = t_window - T_START
    compiles0, compile_s0 = clock.count, clock.seconds
    print("bench: the window opens", file=sys.stderr, flush=True)
    if trace:
        e2e = _traced(run, lambda: driver.traced(run, state, seconds))
    else:
        e2e = driver.window(run, state, seconds)
    in_window = clock.count - compiles0
    print(f"bench: setup_s {setup_s:.6f} (compiles {compiles0}, "
          f"{compile_s0:.3f} s); compilations inside the window: "
          f"{in_window} ({clock.seconds - compile_s0:.3f} s)",
          file=sys.stderr, flush=True)
    run.peak_bytes = peak_bytes()
    device["memory_peak_bytes"] = run.peak_bytes
    compiles1 = clock.count
    checks, attempted, failed = driver.compare(run, state)
    print(f"bench: compilations inside the comparison: "
          f"{clock.count - compiles1}", file=sys.stderr, flush=True)
    metrics = {}
    if trace:
        for m in loaded["per_layer"]:
            reader = load_module(BENCH / "metrics" / f"{m['name']}.py",
                                 f"bench_metric_{m['name']}")
            value = reader.read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
    else:
        e2e = {**e2e, "setup_s": setup_s}
        for m in loaded["end_to_end"]:
            if m["name"] in e2e:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
    correct = all(v["value"] <= v["limit"] for v in checks.values())
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device,
              "window_compiles": in_window}
    if trace:
        result["breakdown"] = run.trace.breakdown()
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace))
    for name, v in result["checks"].items():
        print(f"check {name}: {v['value']} (limit {v['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
