"""Run one cell of ``BENCHMARK.json`` once and print the result line.

Everything that belongs to one configuration, traffic mix or metric is
found by its name:

* ``configs/<config>.json`` (sizes, source, limits; its ``rehearsal``
  sizes and recorded trace for the CPU self-tests; its ``scopes``: each
  driver's phases with their roles, and the job each role is measured
  against) and ``configs/<config>.py`` (its ``System``: how the window
  drives the system under test, the check of what it produced, the
  compiled texts of its programs and the faults the self-tests plant);
* ``traffic/<traffic>.json``, the mix's parameters, read by the loop it
  names under ``"loop"``: ``loops/<loop>.py``, whose ``run`` drives the
  window;
* ``metrics/<metric>.py``, whose ``read(ctx)`` returns the metric's value
  or None when the run has nothing for it to read.
"""

from __future__ import annotations

import argparse
import contextlib
import importlib.util
import json
import os
import shutil
import sys
import time

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_NAME = os.path.basename(BENCH_DIR)
#: seconds of the window a ``--trace 1`` run records
TRACE_SECONDS = 5.0


class Refused(Exception):
    """The run cannot measure this cell here (no chip, unknown device)."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One workload of ``BENCHMARK.json`` with its files resolved."""

    def __init__(self, root: str, workload: str):
        self.root = root
        self.bench = load_json(os.path.join(root, "BENCHMARK.json"))
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if workload not in cells:
            raise Refused(f"no workload {workload!r} in BENCHMARK.json")
        self.spec = cells[workload]
        self.name = workload
        configs = {c["name"]: c for c in self.bench["configs"]}
        self.config_entry = configs[self.spec["config"]]
        self.config = load_json(os.path.join(root,
                                              self.config_entry["file"]))
        self.config_dir = os.path.dirname(
            os.path.join(root, self.config_entry["file"]))
        self.bench_dir = os.path.join(root, BENCH_NAME)
        self.traffic = load_json(os.path.join(
            self.bench_dir, "traffic", self.spec["traffic"] + ".json"))
        self.chips = int(self.spec["chips"])

    def system_class(self):
        name = self.spec["config"]
        mod = load_module(os.path.join(self.config_dir, name + ".py"),
                          f"bench_config_{name}")
        return mod.System

    def loop(self):
        """The window's driver that the traffic names."""
        name = self.traffic["loop"]
        return load_module(os.path.join(self.bench_dir, "loops", name + ".py"),
                           f"bench_loop_{name}").run

    def metrics(self, kind: str):
        """The ``end_to_end`` or ``per_layer`` entries that this cell
        reports."""
        return [m for m in self.bench[kind]
                if self.name in m.get("workloads", [self.name])]

    def reader(self, metric: str):
        path = os.path.join(self.bench_dir, "metrics", metric + ".py")
        return load_module(path, "bench_metric_" + metric.replace(".", "_"))


def check_device(chips: int, peaks_table: dict, allow_cpu: bool):
    import jax

    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu" and not allow_cpu:
        raise Refused(f"no TPU: JAX's first device is {d0.platform!r}")
    if len(devices) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX sees "
                      f"{len(devices)}")
    peaks = peaks_table.get(d0.device_kind)
    if peaks is None and not allow_cpu:
        raise Refused(f"device kind {d0.device_kind!r} is not in peaks.json")
    return devices[:chips], peaks


def memory_peak(devices):
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    return None if None in peaks else max(peaks)


class CompileCounter:
    """Counts backend compiles and persistent-cache lookups, with the host
    time at which each happened."""

    def __init__(self):
        import jax

        self.compiles = []
        self.lookups = []
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles.append(time.perf_counter())

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.lookups.append(time.perf_counter())

    def between(self, t0, t1):
        return {"compiles": sum(t0 <= t <= t1 for t in self.compiles),
                "cache_lookups": sum(t0 <= t <= t1 for t in self.lookups)}


class Hooks:
    """What happens as the window opens and closes: the program's counters
    are read, and with ``--trace 1`` the profiler records the first
    ``TRACE_SECONDS`` of the window inside a host annotation
    ``bench.window`` (a longer trace overflows the profiler's device
    buffer, which then drops operations).  ``stop`` may come early, when
    ``due``; ``done`` is the count of steps finished by then."""

    def __init__(self, system, trace_dir):
        self.system = system
        self.trace_dir = trace_dir
        self.counters = {}
        self._annotation = None

    def start(self):
        if self.trace_dir:
            import jax

            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            self._annotation = jax.profiler.TraceAnnotation("bench.window")
            self._annotation.__enter__()
        self.counters["start"] = dict(self.system.counters(),
                                      t=time.perf_counter())

    def due(self, now: float) -> bool:
        return (self._annotation is not None and "end" not in self.counters
                and now - self.counters["start"]["t"] >= TRACE_SECONDS)

    def stop(self, done=None):
        if "end" in self.counters:
            return
        self.counters["end"] = dict(self.system.counters(),
                                    t=time.perf_counter(), done=done)
        if self._annotation is not None:
            import jax

            self._annotation.__exit__(None, None, None)
            jax.profiler.stop_trace()


def annotator(enabled: bool):
    if not enabled:
        return lambda name: contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation


def verdict(compared: dict, ok: bool, obs: dict) -> bool:
    """``correct``: the system's own checks pass, no step failed, and every
    number compared lies within its limit."""
    return bool(ok) and obs["failed"] == 0 and all(
        v["value"] <= v["limit"] for v in compared.values())


def configure_jax(root: str):
    """JAX's persistent compilation cache where ``JAX_COMPILATION_CACHE_DIR``
    names one, else at the checkout's fixed ``.jax_cache``; every program is
    written to it however short its compile.  The cache's key covers the
    programs' metadata: an executable cached by a tree whose scopes differ
    would otherwise carry that tree's ``op_name``s into the trace's
    reduction."""
    import jax

    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get("JAX_COMPILATION_CACHE_DIR")
                      or os.path.join(root, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)


def scope_readings(red: dict, steps: int, vocab: dict, system,
                   peaks: dict) -> dict:
    """``scopes.readings`` of a traced window, each role measured against
    the least time of its job (``vocab["jobs"]``) at the cell's sizes."""
    from .jobs import least_time_s
    from .scopes import readings

    least = {}
    for role, routine in vocab["jobs"].items():
        job = system.job(routine)
        least[role] = least_time_s(job["flops"], job["bytes"], peaks)[0]
    return readings(red, steps, vocab["drivers"], least)


def run_cell(root: str, workload: str, seed: int, seconds: float,
             trace: bool, t_process: float, allow_cpu: bool = False,
             sizes: dict | None = None,
             peaks_override: dict | None = None) -> dict:
    """Run one cell once; return the result line (a dict).  ``allow_cpu``,
    ``sizes`` and ``peaks_override`` are the CPU rehearsal's; the command
    line never sets them."""
    cell = Cell(root, workload)
    peaks_table = load_json(os.path.join(cell.bench_dir,
                                         "peaks.json"))["devices"]
    t_imp = time.perf_counter()
    devices, peaks = check_device(cell.chips, peaks_table, allow_cpu)
    if peaks_override is not None:
        peaks = peaks_override
    configure_jax(root)
    counter = CompileCounter()
    split = {"imports": time.perf_counter() - t_imp}

    @contextlib.contextmanager
    def phase(name):
        t = time.perf_counter()
        yield
        split[name] = split.get(name, 0.0) + time.perf_counter() - t

    system = cell.system_class()(cell.config, cell.traffic, seed,
                                 sizes=sizes)
    trace_dir = None
    if trace:
        trace_dir = os.path.join(root, ".bench_trace", workload)
        shutil.rmtree(trace_dir, ignore_errors=True)
    try:
        system.setup(phase)
        hooks = Hooks(system, trace_dir)
        obs = cell.loop()(system, system.traffic, seconds, seed, hooks,
                   annotator(trace))
        mem = memory_peak(devices)
        in_window = counter.between(obs["t0"], obs["t_end"])
        split["rest"] = obs["t0"] - t_process - sum(split.values())
        setup_s = obs["t0"] - t_process
        reduced = scoped = None
        if trace:
            from . import scopes
            from .trace import reduce_trace

            reduced = reduce_trace(trace_dir)
            t_red = time.perf_counter()
            scoped = scopes.reduce_scopes(trace_dir, system.program_texts(),
                                          cell.config["scopes"]["drivers"])
            scoped["reduce_s"] = time.perf_counter() - t_red
            shutil.rmtree(trace_dir, ignore_errors=True)
        # the check runs once the window has closed and the peak is read
        t_chk = time.perf_counter()
        compared, ok = system.check(obs)
        check_s = time.perf_counter() - t_chk
    finally:
        system.close()
    correct = verdict(compared, ok, obs)
    ctx = {"setup_s": setup_s, "obs": obs, "counters": hooks.counters,
           "trace": reduced, "scopes": None, "peaks": peaks,
           "job": system.job(), "window_s": obs["window_s"]}
    steps = (hooks.counters.get("end") or {}).get("done")
    if scoped is not None and steps:
        ctx["scopes"] = scope_readings(scoped, steps, cell.config["scopes"],
                                       system, peaks)
    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in cell.metrics(kind):
        value = cell.reader(m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices), "memory_peak_bytes": mem}
    result = {"correct": correct, "attempted": obs["attempted"],
              "failed": obs["failed"], "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["compared"] = compared
    log = sys.stderr
    print("SETUP " + json.dumps({"setup_s": setup_s, **split}), file=log)
    print("WINDOW " + json.dumps({"window_s": obs["window_s"],
                                  "steps": obs.get("steps"),
                                  "completed": obs.get("completed"),
                                  "errors": obs.get("errors", []),
                                  "check_s": check_s, **in_window}),
          file=log)
    if scoped is not None:
        from .scopes import breakdown

        print("SCOPES " + json.dumps({
            **breakdown(scoped), "readings": ctx["scopes"],
            **{k: scoped[k] for k in ("in_program_s", "between_programs_s",
                                      "reduce_s")}}), file=log)
    for name, v in compared.items():
        print(f"CHECK {name} {v['value']!r} limit {v['limit']!r}", file=log)
    log.flush()
    return result


def main(argv=None, t_process: float | None = None) -> int:
    t_process = time.perf_counter() if t_process is None else t_process
    ap = argparse.ArgumentParser(
        description="Run one benchmark cell once and print its result line.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    root = os.path.dirname(BENCH_DIR)
    try:
        result = run_cell(root, args.workload, args.seed, args.seconds,
                          bool(args.trace), t_process)
    except Refused as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0
