"""What a ``--trace 1`` run switches on, and the context the per-layer
readers read.

The program's tracer (host spans) is on for the whole window; the jax
profiler traces, once, the workload's ``trace_s`` seconds that end half
a second before the window closes; the counters that the cell's metric
files name are read at the window's first and last instant. None of
this runs with ``--trace 0``. The program's kernel profiler stays off:
it changes the launch path and no metric reads it.

The device trace sits at the window's end because ``stop_trace`` takes
a minute or more for a busy device's events: there it runs beside the
drain and not beside the window. A metric that finds no whole launch of
its programs in the traced seconds is left out of the line.
"""

from __future__ import annotations

import glob
import importlib
import json
import os
import shutil
import sys
import threading
import time

from . import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
#: how long before the window's close the device trace ends
BEFORE_CLOSE_S = 0.5


def resolve(path: str, harness: dict):
    """``module:attr.attr().key`` -> its value now. ``harness:`` reads
    the harness's own objects."""
    mod, _, rest = path.partition(":")
    obj = harness if mod == "harness" else importlib.import_module(mod)
    for part in rest.split("."):
        call = part.endswith("()")
        name = part[:-2] if call else part
        obj = obj[name] if isinstance(obj, dict) else getattr(obj, name)
        if call:
            obj = obj()
    return obj


class SpanDrain(threading.Thread):
    """Empties the tracer's ring twice a second, so that a window of
    any length keeps every span (the ring holds 16,384)."""

    def __init__(self, tracer):
        super().__init__(name="bench-spans", daemon=True)
        self.tracer = tracer
        self.rows: list = []
        self._halt = threading.Event()

    def run(self) -> None:
        while not self._halt.wait(0.5):
            self.rows.extend(self.tracer.drain_rows())

    def finish(self) -> list:
        self._halt.set()
        self.join(5.0)
        self.rows.extend(self.tracer.drain_rows())
        return self.rows


class Tracing:
    def __init__(self, out_dir: str, seconds: float, metric_files: list,
                 harness: dict, rehearsal: bool, trace_s: float):
        self.out_dir = out_dir
        self.trace_s = min(trace_s, seconds / 3.0)
        self.seconds = seconds
        self.metric_files = metric_files
        self.harness = harness
        self.rehearsal = rehearsal
        self.counters: dict = {}
        self.marks: dict = {}
        self.planes = None
        self.trace_bytes = 0
        self.read_s = 0.0
        self._error = None
        self._thread = None
        self._drain = None
        shutil.rmtree(out_dir, ignore_errors=True)

    def _read_counters(self, when: int) -> None:
        for m in self.metric_files:
            for path in m.get("counters", ()):
                self.counters.setdefault(path, [None, None])[when] = float(
                    resolve(path, self.harness))

    def begin(self) -> None:
        from nomad_tpu.telemetry.trace import tracer

        tracer.enable()
        self._drain = SpanDrain(tracer)
        self._drain.start()
        self._read_counters(0)

    def _profile(self, t0: float) -> None:
        try:
            import jax

            time.sleep(max(t0 + self.seconds - self.trace_s - BEFORE_CLOSE_S
                           - time.monotonic(), 0.0))
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self.out_dir, profiler_options=opts)
            with jax.profiler.TraceAnnotation(trace_reduce.SYNC_NAME):
                self.marks["sync"] = time.monotonic()
            self.marks["lo"] = time.monotonic()
            time.sleep(self.trace_s)
            self.marks["hi"] = time.monotonic()
            jax.profiler.stop_trace()
            self.marks["stopped"] = time.monotonic()
            files = glob.glob(os.path.join(
                self.out_dir, "plugins", "profile", "*", "*.xplane.pb"))
            if not files:
                raise RuntimeError(f"no .xplane.pb under {self.out_dir}")
            self.trace_bytes = os.path.getsize(files[-1])
            t = time.monotonic()
            self.planes = trace_reduce.read_planes(files[-1], self.rehearsal)
            self.read_s = time.monotonic() - t
            if self.planes["sync_trace_s"] is None:
                raise RuntimeError("the trace holds no sync marker")
        except Exception as e:                      # noqa: BLE001
            self._error = e         # raised again by ``end``, in the main thread

    def run_window(self, t0: float) -> None:
        self._thread = threading.Thread(
            target=self._profile, args=(t0,), name="bench-profile",
            daemon=True)
        self._thread.start()

    def end(self) -> None:
        from nomad_tpu.telemetry.trace import tracer

        self._read_counters(1)
        self._thread.join(300.0)
        if self._thread.is_alive():
            raise RuntimeError("the jax profiler did not stop in 300 s")
        if self._error is not None:
            raise self._error
        self.spans = self._drain.finish()
        tracer.disable()

    def context(self, **kw) -> dict:
        """What the readers read: spans of the window, counters at its
        ends, the reduced device trace, the harness's job records."""
        t = time.monotonic()
        t0, t1 = kw["t0"], kw["t1"]
        # rows: name, trace_id, span_id, parent_id, start_s, dur_s,
        # child_s, cpu_s, child_cpu_s, thread
        spans = [r for r in self.spans if t0 <= r[4] and r[4] + r[5] <= t1]
        planes = self.planes
        to_mono = self.marks["sync"] - planes["sync_trace_s"]
        trace = trace_reduce.reduce_trace(
            planes, self.marks["lo"] - to_mono, self.marks["hi"] - to_mono,
            [(r[0], r[4], r[5]) for r in self.spans], to_mono)
        if trace["busy_s"] <= 0:
            raise RuntimeError("no operation ran on the device in the "
                               "traced window")
        shutil.rmtree(self.out_dir, ignore_errors=True)
        self.read_s += time.monotonic() - t
        print(f"trace: {self.trace_bytes} bytes, window "
              f"{trace['window_s']:.2f} s from {self.marks['lo'] - t0:.1f} s "
              f"into the measured one, busy {trace['busy_s']:.3f} s, "
              f"stop_trace took "
              f"{self.marks['stopped'] - self.marks['hi']:.1f} s; programs "
              + json.dumps({k: [len(v), sum(v)] for k, v in
                            trace["launches"].items()}),
              file=sys.stderr, flush=True)
        print("trace_lines: " + json.dumps(
            [row for row in planes["lines_read"] if row[2] > 1000]),
            file=sys.stderr, flush=True)
        names: dict = {}
        for r in spans:
            names[r[0]] = names.get(r[0], 0) + 1
        print("spans_in_window: " + json.dumps(names), file=sys.stderr,
              flush=True)
        print("longest_gaps: " + json.dumps(trace["longest_gaps"]),
              file=sys.stderr, flush=True)
        with open(os.path.join(HERE, "peaks.json")) as f:
            peaks = json.load(f)
        return dict(kw, spans=spans, counters=self.counters, trace=trace,
                    peaks=peaks)
