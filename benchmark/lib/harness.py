"""What every kind of cell shares: finding a cell's files by the names in
``BENCHMARK.json``, the traced sub-window, the per-layer readers, the result
line. Nothing here knows a cell, a configuration or a metric by name.

Adding to the benchmark is adding files and entries:

* a configuration: ``configs/<name>.json`` (``kind`` names its driver under
  ``drivers/``, ``reference`` its plain reference under ``references/``) and
  an entry in ``configs``;
* a traffic mix: ``traffic/<name>.json``; its ``kind`` names the generator
  ``generators/<kind>.py`` that reads it (a new kind of traffic is a new
  generator file, which the drivers take through ``lib/traffic.py``);
* a cell: an entry in ``workloads`` naming a configuration and a mix;
* a per-layer metric: an entry in ``per_layer`` and a reader
  ``layer_metrics/<name>.py`` with ``read(facts) -> number | None``. A name
  ``x.suffix`` without a file of its own is read by ``x.py``: one quantity
  split over cells that report different end-to-end metrics.

One entry a quantity and moved metric, never one a cell. The quantity is
the reader file's name, ``x``. Where ``x`` has one entry and no end-to-end
metric is called ``x``, the entry is ``x``. Where it moves ``tpot_p50_ms``
in some cells and ``ttft_p50_ms`` in others, or is itself an end-to-end
metric's name recorded where it is not judged, each entry is ``x.<moved>``,
``<moved>`` being the moved metric's name up to its first underscore:
``batch_occupancy.tpot``, ``batch_occupancy.ttft``, ``ttft_p50_ms.tpot``
(the first-token time recorded in the cells judged by the token gap).

An entry without ``workloads`` is read in every cell that reports the
metric it ``moves``, so what every serving cell has by construction of the
scheduler, the engine, the pool and the load generator lists no cells: a
new cell joins the moved metric's list in ``end_to_end`` and inherits
them. It adds its cell to the lists of the quantities it shares with some
cells, and entries for what is its own, at the end.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import os
import shutil
import tempfile
import threading
import time

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


def find_cell(bench: dict, name: str):
    """``(cell, config file's content)`` for a workload's name."""
    cells = {c["name"]: c for c in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    cell = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(ROOT, entry["file"])) as fh:
        return cell, json.load(fh)


def metrics_of(bench: dict, group: str, cell_name: str) -> list:
    """The entries of ``end_to_end`` or ``per_layer`` that this cell
    reports: those that list it; an end-to-end metric that lists no cells;
    a per-layer metric that lists none where the cell reports the metric
    it ``moves``."""
    every = [c["name"] for c in bench["workloads"]]
    moved = {m["name"]: m.get("workloads", every)
             for m in bench["end_to_end"]}
    return [m for m in bench[group] if cell_name in m.get(
        "workloads", moved.get(m.get("moves"), every))]


def reader_file(metric_name: str) -> str:
    """The quantity behind a per-layer metric's name: the stem of the file
    under ``layer_metrics/`` that reads it, its own or, for ``x.suffix``
    without one, ``x``."""
    folder = os.path.join(BENCH_DIR, "layer_metrics")
    stems = [metric_name]
    if "." in metric_name:
        stems.append(metric_name.rsplit(".", 1)[0])
    for stem in stems:
        if os.path.exists(os.path.join(folder, f"{stem}.py")):
            return stem
    raise FileNotFoundError(
        f"per-layer metric {metric_name!r} has no reader under {folder}")


def reader_for(metric_name: str):
    """The ``read`` function of a per-layer metric's file."""
    stem = reader_file(metric_name)
    spec = importlib.util.spec_from_file_location(
        "benchmark.layer_metrics." + stem.replace(".", "_"),
        os.path.join(BENCH_DIR, "layer_metrics", f"{stem}.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def driver_for(config: dict):
    return importlib.import_module(f"benchmark.drivers.{config['kind']}")


def reference_for(config: dict):
    return importlib.import_module(
        f"benchmark.references.{config['reference']}")


class Tracer:
    """The traced part of a window: jax's profiler, python tracer off, with
    one host span ``bench:window`` from start to stop that the reduction
    clips to. ``poll`` is called by the load generator on its own thread;
    the trace is written by a helper thread so that the generator does not
    stall on it."""

    def __init__(self, start_s: float, seconds: float):
        self.start_s, self.seconds = start_s, seconds
        self.dir = None
        self._span = None
        self._writer = None
        self.bounds = None  # (t0, t1) on time.monotonic

    def poll(self, now: float, t0: float) -> None:
        rel = now - t0
        if self.dir is None and rel >= self.start_s:
            import jax
            from jax.profiler import ProfileOptions

            opts = ProfileOptions()
            opts.python_tracer_level = 0
            # level 2 also records every piece of every host-side copy
            opts.host_tracer_level = 1
            self.dir = tempfile.mkdtemp(prefix="bench_trace_")
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self._span = jax.profiler.TraceAnnotation("bench:window")
            self._span.__enter__()
            self.bounds = (time.monotonic(), None)
        elif self._span is not None and rel >= self.start_s + self.seconds:
            self.stop()

    def stop(self) -> None:
        import jax

        if self._span is None:
            return
        self._span.__exit__(None, None, None)
        self._span = None
        self.bounds = (self.bounds[0], time.monotonic())
        self._writer = threading.Thread(target=jax.profiler.stop_trace,
                                        name="bench-trace-writer")
        self._writer.start()

    def reduce(self, need_device: bool = True):
        """The reduced trace, or None when nothing was traced (or, in a
        rehearsal on the CPU, when the trace holds no device plane)."""
        import glob

        from benchmark.lib.xplane import reduce_trace

        if self.dir is None:
            return None
        self.stop()
        self._writer.join()
        try:
            found = glob.glob(os.path.join(
                self.dir, "plugins", "profile", "*", "*.xplane.pb"))
            if not found:
                raise RuntimeError("the profiler wrote no .xplane.pb")
            keep = os.environ.get("BENCH_KEEP_TRACE")
            if keep:  # for looking at a trace by hand
                os.makedirs(keep, exist_ok=True)
                shutil.copy(found[-1], keep)
            try:
                return reduce_trace(found[-1])
            except ValueError:
                if need_device:
                    raise
                return None
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest chip, as the backend reports it."""
    import jax

    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices())


def result_line(bench: dict, cell: dict, outcome: dict, trace, traced: bool,
                device: dict, peaks, rehearsal: bool) -> dict:
    """The last line of stdout. ``outcome`` is the driver's: ``correct``,
    ``attempted``, ``failed``, ``end_to_end`` (name → value) and ``facts``
    for the readers."""
    metrics = {}
    if traced:
        facts = dict(outcome["facts"], trace=trace, peaks=peaks,
                     end_to_end=outcome["end_to_end"])
        for m in metrics_of(bench, "per_layer", cell["name"]):
            value = reader_for(m["name"])(dict(facts, metric=m))
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
    else:
        for m in metrics_of(bench, "end_to_end", cell["name"]):
            if m["name"] not in outcome["end_to_end"]:
                raise KeyError(f"the {cell['config']} driver reported no "
                               f"{m['name']} in {cell['name']}")
            metrics[m["name"]] = {
                "value": float(outcome["end_to_end"][m["name"]]),
                "unit": m["unit"]}
    if rehearsal:
        # a CPU run's times are not written under a device metric's name
        sources = {m["name"]: m["source"]
                   for g in ("end_to_end", "per_layer") for m in bench[g]}
        for name, entry in metrics.items():
            if sources[name] != "program_counter":
                entry["value"] = None
    line = {"correct": bool(outcome["correct"]),
            "attempted": int(outcome["attempted"]),
            "failed": int(outcome["failed"]),
            "metrics": metrics, "device": dict(device)}
    if traced and trace is not None:
        line["device"].update(busy_s=trace["busy_s"],
                              window_s=trace["window_s"])
        line["breakdown"] = {"device_ops": trace["device_ops"],
                             "idle_gaps": trace["idle_gaps"]}
    if rehearsal:
        line["rehearsal"] = True
    return line
