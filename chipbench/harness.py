"""What every runner shares: the run's environment (configuration, traffic
parameters, devices, seed), host spans, the profiler window, the count of
compilations, set-up phases, and the lines printed before the result."""
import contextlib
import json
import os
import shutil
import tempfile
import time

from chipbench import trace as trace_mod


def load_json(path):
    with open(path) as f:
        return json.load(f)


class Spans:
    """Host spans around the calls into each layer, recorded from the
    benchmark's own files.  Each is kept as (name, start, end) on
    ``time.perf_counter`` and also written into the profiler's trace
    (``chipbench.<name>``), where it shares the device's clock."""

    def __init__(self):
        self.records = []

    @contextlib.contextmanager
    def span(self, name):
        import jax
        with jax.profiler.TraceAnnotation(trace_mod.SPAN_PREFIX + name):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.records.append((name, t0, time.perf_counter()))

    def wrap(self, name, fn):
        def spanned(*args, **kwargs):
            with self.span(name):
                return fn(*args, **kwargs)
        return spanned

    def durations(self, name, since=0.0):
        return [t1 - t0 for n, t0, t1 in self.records
                if n == name and t0 >= since]


class CompileCount:
    """Backend compilations (cache loads included) in this process, from
    JAX's own monitoring events: exact, and independent of the program's
    telemetry switch."""

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kwargs):
        if event.endswith("backend_compile_duration"):
            self.n += 1


class Tracer:
    """A profiler window a few seconds long inside the measured window.
    ``start``/``stop`` are called from the thread that drives the load,
    outside any span, so every span nests inside ``trace_window``."""

    def __init__(self, wanted, after_s, length_s):
        self.wanted, self.after_s, self.length_s = wanted, after_s, length_s
        self.dir = self._window = self.t_start = None
        self.done = False
        self.rows = []

    def tick(self, now, t_open):
        """Call at a quiet point of the load loop; opens and closes the
        profiler window when their times have come."""
        if not self.wanted or self.done or t_open is None:
            return
        if self.t_start is None:
            if now >= t_open + self.after_s:
                self.start()
        elif now >= self.t_start + self.length_s:
            self.stop()

    def start(self):
        import jax
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0     # spans and device ops only
        self.dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self._window = jax.profiler.TraceAnnotation(trace_mod.WINDOW)
        self._window.__enter__()
        self.t_start = time.perf_counter()

    def stop(self):
        import jax
        if self.t_start is None or self.done:
            return
        self._window.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.done = True
        try:
            self.rows = trace_mod.read_xplane(self.dir)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


class Env:
    """One run of one cell."""

    def __init__(self, root, cell, seed, seconds, traced, t_process,
                 bench=None):
        self.root, self.seed, self.seconds = root, int(seed), float(seconds)
        self.traced, self.t_process = bool(traced), t_process
        self.bench = bench or load_json(os.path.join(root, "BENCHMARK.json"))
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if cell not in cells:
            raise SystemExit("chipbench: no workload %r in BENCHMARK.json"
                             % cell)
        self.cell = cells[cell]
        self.chips = int(self.cell["chips"])
        cfg_entry = {c["name"]: c for c in self.bench["configs"]}[
            self.cell["config"]]
        self.cfg = load_json(os.path.join(root, cfg_entry["file"]))
        self.traffic = load_json(os.path.join(
            root, "chipbench", "traffic", self.cell["traffic"] + ".json"))
        self.spans = Spans()
        self.phases = []
        self.devices = None         # set by run.py once JAX is up
        self.compiles = None
        self.tracer = Tracer(self.traced,
                             self.traffic.get("trace_after_s", 2.0),
                             self.traffic.get("trace_s", 3.0))

    def say(self, key, obj):
        """One line on standard output, before the result line."""
        print("chipbench: %s %s" % (key, json.dumps(obj, sort_keys=True)),
              flush=True)

    @contextlib.contextmanager
    def phase(self, name):
        t0 = time.perf_counter()
        yield
        self.phases.append((name, time.perf_counter() - t0))

    def contexts(self, mx):
        kind = mx.tpu if self.devices[0].platform == "tpu" else mx.cpu
        ctxs = [kind(i) for i in range(self.chips)]
        return ctxs[0] if self.chips == 1 else ctxs

    def shard_rows(self, array):
        """Place an array with its leading axis split over the cell's
        devices (on one chip: on that chip)."""
        import jax
        import numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec
        mesh = Mesh(np.array(self.devices), ("data",))
        return jax.device_put(array, NamedSharding(mesh,
                                                   PartitionSpec("data")))
