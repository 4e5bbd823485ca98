"""One run of one cell: find its files by name, drive it, print the result.

Nothing here knows a configuration, a traffic mix or a metric by name.  A
cell of ``BENCHMARK.json`` names a configuration and a traffic mix:

* ``<config file>``            sizes as run, ``system`` and ``reference``
* ``traffic/<traffic>.json``   the mix: its ``driver`` and parameters
* ``limits/<workload>.json``   the limit of each number ``correct`` compares
* ``metrics/<metric>.py``      one reader per per-layer metric
* ``drivers/<driver>.py``, ``systems/<system>.py``,
  ``reference/<reference>.py``  code, found by the names in the data files

so a later PR adds a configuration, a mix or a metric by adding files.
"""
import importlib
import importlib.util
import json
import os
import shutil
import sys
import time

from . import trace_reduce

PKG = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(PKG)


def log(*parts):
    print(*parts, file=sys.stderr, flush=True)


class Refused(Exception):
    """The run cannot be made here (no chip, unknown cell): exit non-zero
    with no result line."""


def load_json(path):
    with open(path) as f:
        return json.load(f)


class Files:
    """Where a benchmark's data files are: ``root`` holds ``BENCHMARK.json``
    and, under its first ``paths`` entry, the data directories."""

    def __init__(self, root=ROOT):
        self.root = root
        self.bench = load_json(os.path.join(root, "BENCHMARK.json"))
        self.data = os.path.join(root, self.bench["paths"][0])

    def cell(self, workload):
        for w in self.bench["workloads"]:
            if w["name"] == workload:
                return w
        raise Refused(f"BENCHMARK.json has no workload {workload!r}")

    def config(self, name):
        for c in self.bench["configs"]:
            if c["name"] == name:
                return load_json(os.path.join(self.root, c["file"]))
        raise Refused(f"BENCHMARK.json has no config {name!r}")

    def mix(self, traffic):
        return load_json(os.path.join(self.data, "traffic", traffic + ".json"))

    def limits(self, workload):
        return load_json(os.path.join(self.data, "limits", workload + ".json"))

    def metrics(self, group, workload):
        """Entries of ``end_to_end`` or ``per_layer`` this cell reports."""
        return [m for m in self.bench[group]
                if workload in m.get("workloads", [workload])]

    def reader(self, metric):
        """The ``read`` function of ``metrics/<metric>.py``."""
        path = os.path.join(self.data, "metrics", metric + ".py")
        spec = importlib.util.spec_from_file_location(
            "bench_metric_" + metric.replace(".", "_").replace("-", "_"),
            path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read


class CompileLog:
    """Counts programs handed to the backend compiler (compiled or read
    back from the persistent cache) and the cache hits among them."""

    _REQ = "/jax/core/compile/backend_compile_duration"
    _HIT = "/jax/compilation_cache/cache_hits"
    _one = None

    @classmethod
    def get(cls):
        if cls._one is None:
            cls._one = cls()
        return cls._one

    def __init__(self):
        import jax
        self.requests = self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_req)
        jax.monitoring.register_event_listener(self._on_hit)

    def _on_req(self, event, duration, **_):
        self.requests += event == self._REQ

    def _on_hit(self, event, **_):
        self.hits += event == self._HIT


class Tracer:
    """``jax.profiler`` around the last seconds of a window, into a fixed
    directory of the checkout, with the window marked on the host."""

    def __init__(self, out_dir):
        self.dir = out_dir
        self._mark = None
        self.on = False

    def start(self):
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        # starting the profiler stalls the process's other threads for
        # ~0.1 s: mark the window once that has passed
        time.sleep(0.25)
        self._mark = jax.profiler.TraceAnnotation(trace_reduce.WINDOW)
        self._mark.__enter__()
        self.on = True

    def stop(self):
        import jax
        self._mark.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.on = False

    def reduce(self):
        return trace_reduce.reduce(
            trace_reduce.load(trace_reduce.find_xplane(self.dir)))


def device_info(chips, require_tpu):
    import jax
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise Refused(f"needs {chips} TPU chip(s); jax found {len(devs)} "
                      f"{devs[0].platform} device(s)")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def memory_peak(chips):
    """Peak bytes on the fullest chip.  This runtime books a program's
    temporaries under ``peak_bytes_reserved`` where it reports that
    (PERF.md, PR 21), so the two are added."""
    import jax
    peak = 0
    for d in jax.devices()[:chips]:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0))
                   + int(st.get("peak_bytes_reserved", 0)))
    return peak


def peaks_for(files, kind):
    table = load_json(os.path.join(files.data, "peaks.json"))
    return table["by_device_kind"].get(kind)


def judge(numbers, limits):
    """``[(name, value, limit)]`` and whether every value is inside its
    limit.  A number without a limit, or not finite, is not correct."""
    rows, ok = [], True
    for name, value in numbers.items():
        limit = limits.get(name)
        rows.append((name, value, limit))
        inside = (limit is not None and value is not None
                  and value == value and value <= limit)
        ok = ok and inside
    return rows, ok and bool(rows)


def run_cell(workload, seed, seconds, trace, *, files=None, t_start=None,
             require_tpu=True, out_dir=None, control=False):
    """Drive one run; returns the result object (also see :func:`main`).
    ``control``: judge the lower-precision control in the program's place
    (``--control 1``; the driver's runs never ask for it) — ``correct`` has
    to come out false."""
    t_start = time.perf_counter() if t_start is None else t_start
    files = files or Files()
    cell = files.cell(workload)
    cfg = files.config(cell["config"])
    mix = files.mix(cell["traffic"])
    limits = files.limits(workload)
    device = device_info(cell["chips"], require_tpu)
    driver_mod = importlib.import_module(
        f"{__package__}.drivers.{mix['driver']}")
    system_mod = importlib.import_module(
        f"{__package__}.systems.{cfg['system']}")
    ref_mod = importlib.import_module(
        f"{__package__}.reference.{cfg['reference']}")
    out_dir = out_dir or os.path.join(files.root, ".bench_out")
    tracer = Tracer(os.path.join(out_dir, "trace", workload)) if trace \
        else None
    driver = driver_mod.Driver(cfg=cfg, mix=mix, seed=int(seed),
                               system=system_mod, reference=ref_mod,
                               compiles=CompileLog.get(), log=log)
    driver.setup()
    setup_s = time.perf_counter() - t_start
    log(f"[bench] setup_s {setup_s:.3f}")
    run = driver.window(float(seconds), tracer)
    device["memory_peak_bytes"] = memory_peak(cell["chips"])
    run.update(setup_s=setup_s, cfg=cfg, mix=mix, device_kind=device["kind"],
               peaks=peaks_for(files, device["kind"]), trace=None)
    driver.free()
    if tracer is not None:
        run["trace"] = red = tracer.reduce()
        device["busy_s"], device["window_s"] = red["busy_s"], red["window_s"]
    t_check = time.perf_counter()
    numbers = driver.check(control=control)
    rows, correct = judge(numbers, limits)
    log(f"[bench] check took {time.perf_counter() - t_check:.1f} s")

    metrics = {}
    if trace:
        for m in files.metrics("per_layer", workload):
            value = files.reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = dict(run["end_to_end"], setup_s=setup_s)
        for m in files.metrics("end_to_end", workload):
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    result = {"correct": correct, "attempted": run["attempted"],
              "failed": run["failed"], "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = {"device_ops": run["trace"]["device_ops"],
                               "idle_gaps": run["trace"]["idle_gaps"]}
    result["compared"] = {name: {"value": value, "limit": limit}
                          for name, value, limit in rows}
    for name, value, limit in rows:
        log(f"[compared] {name} {value!r} limit {limit!r}")
    log(f"[compared] correct {correct}")
    return result


def main(argv, t_start):
    import argparse
    ap = argparse.ArgumentParser(prog="benchmarks/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), t_start=t_start,
                          control=bool(args.control))
    except Refused as e:
        log(f"[bench] refused: {e}")
        return 3
    print(json.dumps(result), flush=True)
    return 0
