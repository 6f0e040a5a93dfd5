"""What every cell of the benchmark shares: finding its files by name,
the device check, compile counting, peaks and the result line.

Each configuration, traffic mix, per-layer metric and reference lives in
a file of its own, found by the name ``BENCHMARK.json`` gives it:

- ``bench/configs/<config>.json``: the configuration as it is run, and
  ``bench/configs/<config>.py``: how it is handed to the program;
- ``bench/reference/<config>.py``: its plain float32 reference;
- ``bench/traffic/<traffic>.json``: the traffic mix; its ``kind`` names
  the driver, ``bench/drivers/<kind>.py``;
- ``bench/limits/<workload>.json``: the numbers that decide a cell's
  ``correct``, each with its limit;
- ``bench/metrics/<metric>.py``: one per-layer metric's reader.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import math
import os
import sys
import time
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
CACHE_DIR = ROOT / ".jax_cache"


class BenchError(RuntimeError):
    """The cell cannot run here (no chip, missing files, bad spec)."""


# ------------------------------------------------------------ lookups


def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def find(entries, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise BenchError(f"no {what} named {name!r} in BENCHMARK.json")


def load_json(kind: str, name: str) -> dict:
    path = BENCH_DIR / kind / f"{name}.json"
    if not path.is_file():
        raise BenchError(f"no file {path.relative_to(ROOT)}")
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """Import ``bench/<kind>/<name>.py`` by path (names may hold ``-``
    and ``.``, which module names cannot)."""
    path = BENCH_DIR / kind / f"{name}.py"
    if not path.is_file():
        raise BenchError(f"no file {path.relative_to(ROOT)}")
    mod_name = f"bench_{kind}_{name}".replace("-", "_").replace(".", "_")
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One workload of ``BENCHMARK.json`` with its files loaded."""

    def __init__(self, bench: dict, workload: str):
        self.spec = find(bench["workloads"], workload, "workload")
        self.name = workload
        self.chips = int(self.spec["chips"])
        self.config = load_json("configs", self.spec["config"])
        self.config_name = self.spec["config"]
        self.traffic = load_json("traffic", self.spec["traffic"])
        self.binding = load_module("configs", self.spec["config"])
        self.reference = load_module("reference", self.spec["config"])
        self.driver = load_module("drivers", self.traffic["kind"])
        self.end_to_end = [m for m in bench["end_to_end"]
                           if workload in m.get("workloads", [workload])]
        self.per_layer = [m for m in bench["per_layer"]
                          if workload in m.get("workloads", [workload])]


# ------------------------------------------------------------- device


def require_device(platform: str, chips: int):
    """The devices JAX found; raises unless there are ``chips`` of them
    on ``platform``.  There is no fallback to another platform."""
    import jax
    devs = jax.devices()
    if devs[0].platform != platform or len(devs) < chips:
        raise BenchError(
            f"this cell needs {chips} {platform} device(s); JAX found "
            f"{len(devs)} {devs[0].platform} ({devs[0].device_kind})")
    return devs


def device_info(devs, chips: int) -> dict:
    used = devs[:chips]
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in used]
    peaks = [p for p in peaks if p is not None]
    return {"platform": used[0].platform, "kind": used[0].device_kind,
            "count": len(used),
            "memory_peak_bytes": max(peaks) if peaks else None}


def load_peaks(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind`` (``peaks.json``).
    An unknown kind is an error, never a default."""
    with open(BENCH_DIR / "peaks.json") as f:
        table = json.load(f)
    if device_kind not in table["devices"]:
        raise BenchError(f"no peaks for device kind {device_kind!r}; "
                         f"known: {sorted(table['devices'])}")
    return table["devices"][device_kind]


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache in ``.jax_cache/`` of this
    checkout (the program's ``enable_compile_cache`` honours the
    variable), keeping every program, however fast it compiled."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache as program
    cache_dir = program()
    os.makedirs(cache_dir, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache_dir


class CompileClock:
    """Counts JAX's compiles and persistent-cache hits and misses, and
    the seconds it spends compiling, from its ``jax.monitoring``
    events."""

    def __init__(self):
        import jax
        self._jax = jax
        self.seconds = 0.0
        self.compiles = 0
        self.cache = {"hits": 0, "misses": 0}
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, secs, **_kw):
        if event.startswith("/jax/core/compile/"):
            self.seconds += secs
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache["misses"] += 1

    def snapshot(self) -> dict:
        return {"compiles": self.compiles, "compile_s": self.seconds,
                **self.cache}

    def close(self) -> None:
        self._jax.monitoring.unregister_event_duration_listener(self._dur)
        self._jax.monitoring.unregister_event_listener(self._event)


def diff(after: dict, before: dict) -> dict:
    return {k: after[k] - before[k] for k in after}


class HostClock:
    """What the host did in a window, for the runs whose step is host
    work: this process's CPU seconds and the interpreter's garbage
    collections, with the seconds they took."""

    def __init__(self):
        self.gc_n, self.gc_s, self._gc_t0 = 0, 0.0, None
        gc.callbacks.append(self._gc)

    def _gc(self, phase, _info):
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        elif self._gc_t0 is not None:
            self.gc_s += time.perf_counter() - self._gc_t0
            self.gc_n += 1
            self._gc_t0 = None

    def snapshot(self) -> dict:
        t = os.times()
        return {"cpu_s": t.user + t.system, "gc_n": self.gc_n,
                "gc_s": self.gc_s}

    def close(self) -> None:
        gc.callbacks.remove(self._gc)


# ------------------------------------------------------------- output


def log(**line) -> None:
    """An earlier line of the run, on standard error."""
    print(json.dumps(line, default=float), file=sys.stderr, flush=True)


def checks_block(checks: dict) -> dict:
    """``{name: {"value": v, "limit": l}}`` -> the same, with each value
    a float; ``correct`` holds where every value is within its limit."""
    return {k: {"value": float(v["value"]), "limit": float(v["limit"])}
            for k, v in checks.items()}


def all_within(checks: dict) -> bool:
    return all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
               for c in checks.values())


def judge(workload: str, readings: dict):
    """``(checks, correct)``: the numbers that the cell's
    ``bench/limits/<workload>.json`` names, each beside its limit, and
    whether every one is within it.  A run and the control are judged
    by this alone."""
    limits = load_json("limits", workload)
    checks = checks_block({k: {"value": readings[k], "limit": limits[k]}
                           for k in limits})
    return checks, all_within(checks)


def print_checks(checks: dict) -> None:
    for k, c in checks.items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
