"""python3 -m cellbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

One run of one cell of BENCHMARK.json, in one process (a chip belongs
to one process). It refuses at once without a TPU, places the compile
cache, warms the cell's own programs and no others, starts the cell and
runs its traffic unmeasured for a short while (all of that is
`setup_s`), measures for `--seconds`, reads the device's peak memory,
waits for what was in flight, and then — outside the window and outside
set-up — compares what the timed path produced with the plain
reference. Every line but the last is a JSON row that names the device;
the last is the contract's result.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
import time

from cellbench import harness
from cellbench.harness import say


class Tracer:
    """The profiler round the traced part of the window: the last
    `seconds` of it (the workload file's `trace_window_s`; the whole
    window where it gives none), so that stopping it — a minute and
    more where the device logged millions of events — falls after the
    window. Off (`--trace 0`) it does nothing."""

    def __init__(self, on: bool, seconds) -> None:
        self.on, self.seconds = on, seconds
        self.dir = None
        self.t0 = self.t1 = None

    def start(self) -> None:
        if not self.on or self.t0 is not None:
            return
        import jax
        self.dir = tempfile.mkdtemp(prefix="cellbench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0        # our spans, not every frame
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        with span("trace_open"):
            self.t0 = time.monotonic()

    def due(self, t_close: float) -> bool:
        """Time to start, for a window that closes at `t_close`?"""
        return (self.on and self.t0 is None
                and (self.seconds is None
                     or time.monotonic() >= t_close - self.seconds))

    def stop(self) -> None:
        if self.t0 is None or self.t1 is not None:
            return
        import jax
        with span("trace_close"):
            self.t1 = time.monotonic()
        jax.profiler.stop_trace()

    def reduce(self, chips: int):
        from cellbench import trace
        try:
            return trace.reduce(trace.read_xplane(trace.find_xplane(self.dir)),
                                chips=chips)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def span(name: str):
    """A benchmark span on the profiler's clock (it costs nothing when
    no trace is being taken)."""
    import jax
    return jax.profiler.TraceAnnotation("cellbench:" + name)


def run_cell(cell, seed: int, seconds: float, trace: bool,
             require_tpu: bool = True) -> dict:
    """One run of `cell` (a workload's name, or a harness.Cell);
    returns the result's object. `require_tpu=False` is for rehearsals
    and tests on XLA-CPU, which call this and never main()."""
    if isinstance(cell, str):
        cell = harness.Cell(cell)
    name = cell.name
    import jax
    device = harness.device_info()
    if require_tpu and (device["platform"] != "tpu"
                        or device["count"] < cell.chips):
        raise SystemExit(
            f"cellbench needs {cell.chips} TPU chip(s) for {name}: JAX "
            f"found {device['count']} device(s) of platform "
            f"{device['platform']!r}")
    from tpubft.utils.jaxcache import setup_cache
    cache_dir = setup_cache()
    # the sub-second programs are persisted too, so that a second run
    # finds every program in the cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    log = harness.CompileLog()
    say(phase="start", cell=name, seed=seed, seconds=seconds,
        trace=int(trace), compile_cache=cache_dir,
        jax=jax.__version__)

    driver = harness.load_by_name(
        "drivers", cell.config["driver"]).Driver(cell, seed, log)
    tracer = Tracer(trace, cell.workload.get("trace_window_s"))
    cmp = harness.Comparisons()
    try:
        driver.setup()
        compiles0 = len(log.rows)
        setup_s = harness.process_age_s()
        say(phase="setup", setup_s=round(setup_s, 3),
            programs=[dict(fun=r[0], s=r[1], cache_hit=r[2])
                      for r in log.rows if r[1] >= 0.5])
        driver.measure(seconds, tracer)
        tracer.stop()
        in_window = log.rows[compiles0:]
        peak = harness.memory_peak_bytes()
        driver.finish()
        t0 = time.monotonic()
        driver.check(cmp)
        cmp.add("compiles_in_window", len(in_window), 0)
        say(phase="check",
            check_s=round(time.monotonic() - t0, 3),
            compiled_in_window=[r[0] for r in in_window])
        values = dict(driver.end_to_end(), setup_s=setup_s)
        dev = dict(device, memory_peak_bytes=peak)
        result = {"correct": cmp.correct, "attempted": driver.attempted,
                  "failed": driver.failed}
        if trace:
            reduced = tracer.reduce(cell.chips)
            dev.update(busy_s=reduced["busy_s"],
                       window_s=reduced["window_s"])
            ctx = dict(driver.layer_context(), trace=reduced, cell=cell,
                       device_kind=device["kind"])
            metrics = {}
            for m in cell.per_layer():
                value = harness.load_by_name(
                    "layer_metrics", m["name"]).read(ctx)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
            result["breakdown"] = reduced["breakdown"]
            say(phase="trace", end_to_end=values,
                kernels=reduced["kernels"])
        else:
            metrics = {m["name"]: {"value": values[m["name"]],
                                   "unit": m["unit"]}
                       for m in cell.end_to_end() if m["name"] in values}
        result.update(metrics=metrics, device=dev, compared=cmp.rows)
        # `compared` comes last in the line, and `breakdown` before it
        order = ["correct", "attempted", "failed", "metrics", "device",
                 "breakdown", "compared"]
        return {k: result[k] for k in order if k in result}
    finally:
        tracer.stop()
        driver.close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace))
    sys.stdout.flush()
    for name, row in result["compared"].items():
        print(f"compared {name} = {row['value']} (limit {row['limit']})"
              f"{'' if row['value'] <= row['limit'] else '  <-- FAILS'}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
