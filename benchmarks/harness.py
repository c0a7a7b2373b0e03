"""What every kind of cell shares: the run's context, set-up accounting
(``CompileMeter``, copied from chip_smoke.py), the device record, the table of
peaks, the profiler window, and the small statistics.  No cell, model or
traffic is named here; a kind (``kinds/<kind>.py``) gets a :class:`Run` and
returns a :class:`Result`.
"""

from __future__ import annotations

import dataclasses
import gc
import glob
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))

#: the one end-to-end metric every cell reports; the harness measures it
SETUP = "setup_s"


def load_json(*parts: str):
    with open(os.path.join(HERE, *parts)) as fh:
        return json.load(fh)


def load_module(folder: str, name: str):
    """``benchmarks/<folder>/<name>.py`` by file path: names carry dots and
    dashes (``mfu.train``), which no import statement takes."""
    path = os.path.join(HERE, folder, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {folder}/{name}.py under {HERE}")
    modname = "benchmarks_%s_%s" % (folder, "".join(
        c if c.isalnum() else "_" for c in name))
    if modname in sys.modules:
        return sys.modules[modname]
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[modname] = mod
    spec.loader.exec_module(mod)
    return mod


_T0 = time.perf_counter()


def log(msg: str):
    """An earlier line of the run: anything but the result.  Stamped with
    the seconds since this module was imported (about the process's age)."""
    print(f"[bench +{time.perf_counter() - _T0:.1f}s] {msg}", flush=True)


def seed_key(seed: int):
    """A PRNG key from any whole number up to 2**63 (the driver's seeds pass
    2**31, which a 32-bit key seed does not hold)."""
    import jax
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


class CompileMeter:
    """Set-up work apart from run work, from JAX's own events: the trace,
    lowering and backend-compile durations (a persistent-cache hit reports
    its retrieval under the last), the programs compiled, and cache hits and
    misses.  One listener for the process: compiles on any thread count."""

    _SETUP = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self._lock = threading.Lock()
        self._total = {"compile_s": 0.0, "programs": 0, "cache_hits": 0,
                       "cache_misses": 0}
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **_):
        if name in self._SETUP:
            with self._lock:
                self._total["compile_s"] += secs
                self._total["programs"] += name.endswith(
                    "backend_compile_duration")

    def _event(self, name, **_):
        key = name.rsplit("/", 1)[-1]
        if key in ("cache_hits", "cache_misses"):
            with self._lock:
                self._total[key] += 1

    def snapshot(self) -> dict:
        with self._lock:
            return dict(self._total)


def device_peaks(kind: str) -> dict:
    """Peaks of one chip of ``kind`` from peaks.json.  A kind that is not in
    the table is an error, not a default."""
    table = load_json("peaks.json")["chips"]
    if kind not in table:
        raise KeyError(f"device_kind {kind!r} is not in benchmarks/peaks.json; "
                       "add it with the source of its peaks")
    return table[kind]


class TraceWindow:
    """One profiler trace around the measured window of a ``--trace 1`` run:
    device and host-annotation events only (the Python tracer would swamp a
    host loop), written under TMPDIR, reduced, and removed."""

    def __init__(self, enabled: bool):
        self.enabled = enabled
        self.reduction: dict | None = None
        self._dir: str | None = None
        self._t0 = 0.0

    def start(self):
        if not self.enabled:
            return
        import jax
        self._dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self._dir, profiler_options=opts)
        self._t0 = time.perf_counter()

    def stop(self, devices):
        if not self.enabled or self._dir is None:
            return
        import jax
        window_s = time.perf_counter() - self._t0
        jax.profiler.stop_trace()
        try:
            paths = glob.glob(os.path.join(
                self._dir, "plugins", "profile", "*", "*.xplane.pb"))
            if not paths:
                raise RuntimeError(f"the profiler wrote no .xplane.pb under "
                                   f"{self._dir}")
            import trace_reduce
            layout: dict = {}
            events = trace_reduce.load_xplane(paths[0], layout)
            log("trace layout: " + json.dumps({
                p: {k: v for k, v in sorted(
                    ls.items(), key=lambda kv: -kv[1])[:6]}
                for p, ls in layout.items()}))
            self.reduction = trace_reduce.reduce_events(
                events, window_s=window_s, device_ids=[d.id for d in devices])
            log("device ops [name, self s, count, whole s] by self time: "
                + json.dumps([[n, round(v[0], 6), v[1], round(v[2], 6)]
                              for n, v in sorted(
                                  self.reduction["ops"].items(),
                                  key=lambda kv: -kv[1][0])[:40]]))
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)
            self._dir = None


class HostProbe:
    """What the HOST did during a window, to tell a stall of the machine from
    one of the program: a thread that sleeps ``period`` seconds at a time and
    notes by how much it overslept (a host that is paused or starved wakes
    every thread late, whatever the device does), the garbage collector's
    time, the kernel's count of CPU time stolen by the hypervisor, and the
    load average.  For an earlier line of the run; no metric reads it."""

    def __init__(self, period: float = 0.005):
        self.period = period
        self.report: dict = {}

    def __enter__(self):
        self._stop = threading.Event()
        self._late, self._late_at, self._gc_s, self._gc_n = 0.0, 0.0, 0.0, 0
        self._t0 = time.perf_counter()
        self._steal0 = self._stolen()
        gc.callbacks.append(self._on_gc)
        self._thread = threading.Thread(target=self._sleeper, daemon=True)
        self._thread.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._thread.join()
        gc.callbacks.remove(self._on_gc)
        steal1 = self._stolen()
        self.report = {
            "sleeper_late_max_s": self._late, "late_at_s": self._late_at,
            "gc_s": self._gc_s, "gc_collections": self._gc_n,
            "cpu_stolen_s": None if None in (steal1, self._steal0)
            else steal1 - self._steal0,
            "loadavg": list(os.getloadavg())}

    def _sleeper(self):
        while not self._stop.is_set():
            t = time.perf_counter()
            time.sleep(self.period)
            late = time.perf_counter() - t - self.period
            if late > self._late:
                self._late, self._late_at = late, t - self._t0

    def _on_gc(self, phase, info):
        if phase == "start":
            self._gc_t = time.perf_counter()
        else:
            self._gc_s += time.perf_counter() - self._gc_t
            self._gc_n += 1

    @staticmethod
    def _stolen():
        """Seconds of CPU the hypervisor gave to others (``steal`` of
        /proc/stat's first line, all cores together); None off Linux."""
        try:
            with open("/proc/stat") as fh:
                return int(fh.readline().split()[8]) / os.sysconf("SC_CLK_TCK")
        except (OSError, IndexError, ValueError):
            return None


@dataclasses.dataclass
class Result:
    """What a kind hands back.  ``end_to_end``: metric name -> value, as
    measured.  ``window``: whatever the per-layer readers of this kind of
    cell take their numbers from (counts, clocks, snapshots).  ``compared``:
    every number the kind's check compared, ``{name: [value, limit]}``."""
    correct: bool
    attempted: int
    failed: int
    end_to_end: dict
    window: dict = dataclasses.field(default_factory=dict)
    compared: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Run:
    """One run of one cell."""
    cell: dict                  # the manifest's workload entry
    config: dict                # configs/<config>.json
    workload: dict              # workloads/<cell>.json
    seed: int
    seconds: float
    trace: TraceWindow
    devices: list               # the chips this cell uses
    peaks: dict                 # this device_kind's entry of peaks.json
    meter: CompileMeter
    t_process: float            # perf_counter at process start
    setup_s: float | None = None
    setup_meter: dict | None = None
    closed_s: float = float("inf")      # the window's end, as setup_s

    @property
    def family(self):
        return load_module("families", self.config["family"])

    @property
    def reference(self):
        return load_module("reference", self.config["family"])

    def window_seconds(self) -> float:
        """A traced run measures a short window: traces are large and the
        tracer slows the host; its numbers are per-layer ones only."""
        if self.trace.enabled:
            return min(self.seconds,
                       float(self.workload.get("trace_seconds", 5)))
        return self.seconds

    def open_window(self):
        """Set-up ends here: everything is built, checked and warm."""
        self.setup_s = time.perf_counter() - self.t_process
        self.setup_meter = self.meter.snapshot()
        log(f"set-up {self.setup_s:.2f}s: {json.dumps(self.setup_meter)}")
        self.trace.start()

    def close_window(self) -> dict:
        """Ends the traced window; returns what compiled INSIDE the window
        (all zeros in a sound run)."""
        self.closed_s = time.perf_counter() - self.t_process
        self.trace.stop(self.devices)
        after = self.meter.snapshot()
        inside = {k: after[k] - self.setup_meter[k] for k in after}
        log(f"compiled inside the window: {json.dumps(inside)}")
        return inside


def device_record(devices, trace: TraceWindow) -> dict:
    """The result line's ``device``.  ``memory_peak_bytes`` is the fullest
    chip's ``peak_bytes_in_use``, which leaves out what a loaded program
    RESERVES for its temporaries: that counter is logged beside it."""
    d0 = devices[0]
    stats = [d.memory_stats() or {} for d in devices]
    peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
    log(f"memory: peak_bytes_in_use {peak}, peak_bytes_reserved "
        f"{max(s.get('peak_bytes_reserved', 0) for s in stats)}, "
        f"bytes_limit {max(s.get('bytes_limit', 0) for s in stats)}")
    import jax
    rec = {"platform": d0.platform, "kind": d0.device_kind,
           "count": len(jax.devices()), "memory_peak_bytes": int(peak)}
    if trace.reduction is not None:
        rec["busy_s"] = trace.reduction["busy_s"]
        rec["window_s"] = trace.reduction["window_s"]
    return rec
