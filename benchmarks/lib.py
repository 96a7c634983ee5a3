"""Shared pieces of the benchmark harness: finding files by name, the
compile clock, the device stamp, the compile cache, and the arithmetic
(rates, shares, percentiles, mfu) that the metrics are made of.

Everything here is the yardstick's own: the program under test is not
imported by this module."""

from __future__ import annotations

import importlib.util
import json
import os
import statistics

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class BenchFailure(RuntimeError):
    """The run cannot give a result.  Never caught: the process exits
    non-zero and prints no last line."""


# ------------------------------------------------------ files by name
def load_json(kind: str, name: str) -> dict:
    """``benchmarks/<kind>/<name>.json``."""
    path = os.path.join(HERE, kind, name + ".json")
    if not os.path.isfile(path):
        raise BenchFailure(f"no {kind} file {path}")
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``benchmarks/<kind>/<name>.py`` as a module (names may hold dots
    and dashes, so no ``import`` statement could name them)."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.isfile(path):
        raise BenchFailure(f"no {kind} file {path}")
    safe = "".join(c if c.isalnum() else "_" for c in name)
    spec = importlib.util.spec_from_file_location(
        f"benchmarks_{kind}_{safe}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_benchmark(held: bool = False) -> dict:
    """``BENCHMARK.json``; with ``held``, each ``held/<cell>.json``'s
    entries appended to its lists: a cell whose harness is here and
    tested but which is not admitted yet (the file says why).  The
    driver runs what ``BENCHMARK.json`` names and nothing else."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    held_dir = os.path.join(HERE, "held")
    if held and os.path.isdir(held_dir):
        for name in sorted(os.listdir(held_dir)):
            if name.endswith(".json"):
                with open(os.path.join(held_dir, name)) as f:
                    more = json.load(f)
                for key in ("configs", "workloads", "end_to_end",
                            "per_layer"):
                    bench[key] = bench[key] + more.get(key, [])
    return bench


def metrics_for(bench: dict, section: str, workload: str) -> list:
    """The entries of ``end_to_end`` / ``per_layer`` this cell reports:
    those without a ``workloads`` key, and those that list the cell."""
    return [m for m in bench[section]
            if "workloads" not in m or workload in m["workloads"]]


def with_tiny(d: dict, tiny: bool) -> dict:
    """The file's sizes, with its ``tiny`` overrides applied for the CPU
    rehearsal.  Nested groups are merged one level deep."""
    out = {k: v for k, v in d.items() if k != "tiny"}
    if tiny:
        for k, v in d.get("tiny", {}).items():
            if isinstance(v, dict) and isinstance(out.get(k), dict):
                out[k] = {**out[k], **v}
            else:
                out[k] = v
    return out


# ------------------------------------------------------------- device
def device_stamp(jax) -> dict:
    """The device as JAX reports it; on every result."""
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": jax.device_count()}


def peaks_for(kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if kind not in table:
        raise BenchFailure(
            f"no published peaks for device_kind {kind!r} in "
            f"benchmarks/peaks.json (known: {sorted(table)})")
    return table[kind]


def enable_compile_cache(jax) -> str:
    """JAX's persistent compilation cache at the FIXED path
    ``<checkout>/.jax_cache``, whatever the environment says: the
    driver's two sides may share nothing, and a cache directory that the
    machine caps (PR 21 met one of 192 MiB) evicts an 84 MB train step.
    Every program is stored, however quick its compile."""
    path = os.path.join(ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_compilation_cache_max_size", -1)
    return path


class CompileClock:
    """Compile seconds and persistent-cache traffic from JAX's own
    monitoring events (copied from chip_smoke.py, PR 21)."""

    _COMPILE = "/jax/core/compile/"
    _CACHE = "/jax/compilation_cache/"

    def __init__(self, jax):
        self._jax = jax
        self.compile_s = 0.0
        self.backend_compiles = 0
        self.counts = {"compile_requests_use_cache": 0, "cache_hits": 0,
                       "cache_misses": 0}
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **_kw):
        if event.startswith(self._COMPILE):
            self.compile_s += duration
            if event.endswith("backend_compile_duration"):
                self.backend_compiles += 1

    def _event(self, event, **_kw):
        if event.startswith(self._CACHE):
            key = event[len(self._CACHE):]
            if key in self.counts:
                self.counts[key] += 1

    def mark(self) -> dict:
        return {"compile_s": self.compile_s,
                "backend_compiles": self.backend_compiles, **self.counts}

    def since(self, mark: dict) -> dict:
        now = self.mark()
        return {k: now[k] - mark[k] for k in now}

    def close(self):
        m = self._jax.monitoring
        m.unregister_event_duration_listener(self._dur)
        m.unregister_event_listener(self._event)


def memory_peak_bytes(jax, devices, program_bytes: int) -> dict:
    """Peak device memory on the fullest chip.  Two counts, and the
    larger is reported: the runtime's ``peak_bytes_in_use`` (on this
    runtime it leaves out a program's temporaries: PR 21 read 0.67 GB
    beside a step with 9.2 GB of them) and the compiler's count for the
    cell's main program (arguments + outputs - aliased + temporaries,
    per device)."""
    runtime = 0
    for d in devices:
        stats = d.memory_stats() or {}
        runtime = max(runtime, int(stats.get("peak_bytes_in_use", 0)))
    return {"runtime_peak_bytes_in_use": runtime,
            "compiler_program_bytes": int(program_bytes),
            "memory_peak_bytes": max(runtime, int(program_bytes))}


# --------------------------------------------------------- arithmetic
def rate(count: float, seconds: float) -> float:
    if seconds <= 0:
        raise BenchFailure(f"rate over {seconds} s")
    return count / seconds


def phase_share(observed: dict, category: str):
    """Share of the host part of the window spent in the product's
    telemetry spans of one category; None when there are none."""
    phases = observed.get("phase_seconds") or {}
    if category not in phases:
        return None
    return phases[category] / observed["host_window_s"]


def mfu_percent(flops_per_record: float, records_per_s: float,
                chips: int, peak_flops_per_s: float) -> float:
    """Model FLOP/s utilization: the operations the forward and backward
    passes need per record, times records per second, over chips times
    the published peak.  An end-to-end utilization, not a roofline
    share."""
    return 100.0 * flops_per_record * records_per_s / (
        chips * peak_flops_per_s)


def iqr_spread(values) -> float:
    """(Q3 - Q1) / median with ``statistics.quantiles(values, n=4)`` —
    the spread the bounds are set from."""
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
