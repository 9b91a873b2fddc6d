"""The runner: finds a cell's files by the names in ``BENCHMARK.json``,
runs its traffic kind once, and builds the contract's result line.

Nothing here knows a cell, a configuration, a traffic mix or a metric by
name: a cell names its configuration (``benchmark/configs/<name>.json``)
and its traffic (``benchmark/traffic/<name>.json``), the traffic file
names its kind (``benchmark/kinds/<kind>.py``), the configuration names
its reference (``benchmark/references/<name>.py``), and each per-layer
metric of the manifest is ``benchmark/layer_metrics/<metric>.py``.
"""
from __future__ import annotations

import importlib.util
import json
import os
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_manifest():
    return load_json(ROOT, "BENCHMARK.json")


def load_module(folder: str, name: str):
    """``benchmark/<folder>/<name>.py`` as a module; names may hold dots."""
    path = os.path.join(BENCH, folder, name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_%s_%s" % (folder, name.replace(".", "_").replace("-", "_")),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def by_name(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit("bench: no %s named %r in BENCHMARK.json"
                     % (what, name))


def cell_files(manifest, workload):
    """-> the cell's manifest entry, its configuration and its traffic."""
    wl = by_name(manifest["workloads"], workload, "workload")
    config = load_json(ROOT, by_name(manifest["configs"], wl["config"],
                                     "configuration")["file"])
    traffic = load_json(BENCH, "traffic", wl["traffic"] + ".json")
    return wl, config, traffic


def metrics_of(manifest, group, workload):
    """The manifest's metrics of a group that this cell reports."""
    return [m for m in manifest[group]
            if "workloads" not in m or workload in m["workloads"]]


class Cell:
    """One run of one cell: what a traffic kind and a metric reader get."""

    def __init__(self, pt, config, traffic, seed, seconds, trace, chips,
                 devices, peaks, compiles, t_process_start):
        self.pt, self.config, self.traffic = pt, config, traffic
        self.seed, self.seconds, self.trace = int(seed), float(seconds), \
            bool(trace)
        self.chips, self.devices, self.peaks = chips, devices, peaks
        self.compiles = compiles
        self.t_process_start = t_process_start
        self.t_window = None
        self.reference = load_module("references", config["reference"])

    def log(self, msg):
        print("bench: " + msg, flush=True)

    def open_window(self):
        self.t_window = time.perf_counter()
        self.compiles.window_open = True

    def close_window(self):
        self.compiles.window_open = False


def memory_peak_bytes(devices):
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks)) if peaks else 0


def run_cell(cell) -> dict:
    kind = load_module("kinds", cell.traffic["kind"])
    record = kind.run(cell)
    record["end_to_end"]["setup_s"] = cell.t_window - cell.t_process_start
    record["memory_peak_bytes"] = memory_peak_bytes(cell.devices)
    record["compiles_in_window"] = cell.compiles.in_window
    if cell.compiles.in_window:
        record["problems"].append("%d compile(s) inside the window"
                                  % cell.compiles.in_window)
    cell.log("compiles: %d in all, %d inside the window; persistent cache "
             "hits %d, misses %d"
             % (cell.compiles.total, cell.compiles.in_window,
                cell.compiles.cache_hits, cell.compiles.cache_misses))
    return record


def result_line(cell, record, manifest, workload, device) -> dict:
    """The contract's last line. ``--trace 0``: the cell's end-to-end
    metrics; ``--trace 1``: its per-layer metrics, each from its reader
    (a reader that finds nothing returns None and is left out)."""
    metrics = {}
    if cell.trace:
        for m in metrics_of(manifest, "per_layer", workload):
            v = load_module("layer_metrics", m["name"]).read(record, cell)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        for k, v in record["end_to_end"].items():
            cell.log("end to end in the traced run (not judged): %s = %r"
                     % (k, v))
    else:
        for m in metrics_of(manifest, "end_to_end", workload):
            if m["name"] in record["end_to_end"]:
                metrics[m["name"]] = {
                    "value": record["end_to_end"][m["name"]],
                    "unit": m["unit"]}
    device = dict(device, memory_peak_bytes=max(
        record["memory_peak_bytes"],
        record.get("memory_analysis_bytes") or 0))
    out = {}
    if cell.trace:
        trace = record.get("trace")
        if not trace or not trace["busy_s"] > 0:
            record["problems"].append("no device operation in the trace")
        else:
            device.update(busy_s=trace["busy_s"],
                          window_s=trace["window_s"])
            out["breakdown"] = trace["breakdown"]
    for p in record["problems"]:
        cell.log("NOT CORRECT: " + p)
    return dict(correct=not record["problems"],
                attempted=record["attempted"], failed=record["failed"],
                metrics=metrics, device=device, **out)
