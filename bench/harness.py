"""Runs one cell of ``BENCHMARK.json`` and builds its result line.

Every piece is found by name, so a new cell, configuration, traffic mix
or per-layer metric is a new file plus an entry in ``BENCHMARK.json``:

* ``BENCHMARK.json`` -> the cell (``workloads``), its configuration's file
  (``configs``), the metrics it reports (``end_to_end``, ``per_layer``);
* ``<bench>/traffic/<mix>.json`` -> the traffic, whose ``kind`` names a
  load class in ``bench/loads.py`` or, for a new kind, ``traffic/<kind>.py``
  defining ``Load``;
* ``<bench>/metrics/<metric>.py`` -> ``read(record)``, the reducer of one
  per-layer metric, which returns a number or None when the run holds
  nothing for it to read.

The configuration file states the limits of the comparison that decides
``correct`` (``check_limits``).
"""
from __future__ import annotations

import importlib.util
import json
import os
import shutil
import sys
from pathlib import Path

from bench.loads import CLOCK, KINDS, log


class UnknownName(LookupError):
    """A cell, configuration, traffic mix or metric that is not there."""


class CompileClock:
    """Seconds JAX spends in backend compilation (or fetching a compiled
    program from the persistent cache), and the cache hits among them."""

    def __init__(self):
        import jax

        self.seconds, self.programs, self.cache_hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.programs += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def report(self) -> str:
        return (f"{self.seconds:.1f} s over {self.programs} programs "
                f"({self.cache_hits} persistent-cache hits)")


class Catalog:
    """The benchmark's files under ``root`` (the checkout)."""

    def __init__(self, root):
        self.root = Path(root)
        spec = self.root / "BENCHMARK.json"
        if not spec.is_file():
            raise UnknownName(f"no BENCHMARK.json in {self.root}")
        self.spec = json.loads(spec.read_text())
        self.bench = self.root / self.spec["paths"][0]

    def _entry(self, key: str, name: str) -> dict:
        for e in self.spec[key]:
            if e["name"] == name:
                return e
        raise UnknownName(f"BENCHMARK.json has no {key} entry {name!r}; "
                          f"known: {[e['name'] for e in self.spec[key]]}")

    def cell(self, name: str) -> dict:
        return self._entry("workloads", name)

    def config(self, name: str) -> dict:
        return json.loads((self.root / self._entry("configs", name)["file"])
                          .read_text())

    def traffic(self, name: str) -> dict:
        path = self.bench / "traffic" / f"{name}.json"
        if not path.is_file():
            raise UnknownName(f"no traffic file {path}")
        return json.loads(path.read_text())

    def load_kind(self, kind: str):
        if kind in KINDS:
            return KINDS[kind]
        path = self.bench / "traffic" / f"{kind}.py"
        if not path.is_file():
            raise UnknownName(f"no traffic kind {kind!r}: not in "
                              f"{sorted(KINDS)} and no {path}")
        return _load(path, f"bench_kind_{kind}").Load

    def end_to_end(self, cell: str) -> list:
        return [m for m in self.spec["end_to_end"]
                if cell in m.get("workloads", [cell])]

    def per_layer(self, cell: str) -> list:
        moves = {m["name"] for m in self.end_to_end(cell)}
        return [m for m in self.spec["per_layer"]
                if cell in m.get("workloads", [cell] if m["moves"] in moves
                                 else [])]

    def reader(self, metric: str):
        path = self.bench / "metrics" / f"{metric}.py"
        if not path.is_file():
            raise UnknownName(f"no reader {path} for metric {metric!r}")
        return _load(path, "bench_metric_" + metric.replace(".", "_")).read


def _load(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def use_compile_cache(root: Path) -> str:
    """JAX's persistent cache at a fixed path inside the checkout, unless
    ``JAX_COMPILATION_CACHE_DIR`` names one; every program is kept, so a
    second run of a cell compiles nothing."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        Path(root) / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def device_info() -> dict:
    import jax

    devs = jax.devices()
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def checks_of(numbers: dict, limits: dict) -> dict:
    """Each compared number beside its limit; a number with no limit is
    an error in the configuration, never a pass."""
    out = {}
    for k, v in numbers.items():
        if k not in limits:
            raise UnknownName(f"no check_limits entry for {k!r}")
        out[k] = {"value": v, "limit": limits[k]}
    return out


def run_cell(catalog: Catalog, name: str, seed: int, seconds: float,
             trace: bool, *, t0: float | None = None) -> dict:
    """Set up, measure, check and reduce one cell; returns the result
    line's object. ``t0`` is the clock at process start, where set-up
    begins."""
    t0 = CLOCK() if t0 is None else t0
    cell = catalog.cell(name)
    config = catalog.config(cell["config"])
    traffic = catalog.traffic(cell["traffic"])
    load = catalog.load_kind(traffic["kind"])(config, traffic, seed)
    clock = CompileClock()
    with load.spans("setup"):
        load.setup(seconds)
        mark = warm_marker() if trace else None
    setup_s = CLOCK() - t0
    log(f"set-up: {setup_s:.3f} s; compile {clock.report()}")

    before = (clock.programs, clock.cache_hits)
    if trace:
        trace_dir = catalog.root / ".bench_trace" / name
        e2e, host = traced_window(load, seconds, trace_dir, mark)
    else:
        e2e = load.window(seconds)
    in_window = (clock.programs - before[0], clock.cache_hits - before[1])
    log(f"compiles inside the window: {in_window[0]} programs "
        f"({in_window[1]} from the persistent cache)")
    device = device_info()
    record = load.record()
    load.release()

    t = CLOCK()
    attempted, failed, numbers = load.check()
    log(f"reference check: {CLOCK() - t:.2f} s; {failed} of {attempted} "
        f"answers past their limits")
    checks = checks_of(numbers, config["check_limits"])
    correct = failed == 0 and all(c["value"] <= c["limit"]
                                  for c in checks.values())

    metrics: dict = {}
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if not trace:
        e2e["setup_s"] = setup_s
        for m in catalog.end_to_end(name):
            if m["name"] not in e2e:
                raise UnknownName(f"cell {name!r} reports no {m['name']!r}")
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    else:
        from bench import roofline, trace as tr

        t = CLOCK()
        planes = tr.load(tr.find_xplane(trace_dir))
        off = tr.host_offset_ns(planes, *host)
        reduced = tr.reduce(planes, [(n, a * 1e9 - off, b * 1e9 - off)
                                     for n, a, b in load.spans.events])
        log(f"trace reduced in {CLOCK() - t:.2f} s: window "
            f"{reduced['window_s']:.3f} s, busy {reduced['busy_s']:.3f} s")
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        out["breakdown"] = tr.breakdown(reduced)
        rec = {"cell": name, "config": config, "traffic": traffic,
               "kind": traffic["kind"], "trace": reduced,
               "peaks": roofline.peaks(device["kind"]), "run": record}
        for m in catalog.per_layer(name):
            value = catalog.reader(m["name"])(rec)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
    out["checks"] = checks
    return out


def bench_mark(x):
    """The marker program that brackets a traced window (``trace.MARK``)."""
    return x + 1


def warm_marker():
    """The compiled marker, run once so the window compiles nothing."""
    import jax
    import jax.numpy as jnp

    mark = jax.jit(bench_mark)
    x = jnp.zeros((8,), jnp.float32)
    mark(x).block_until_ready()
    return lambda: mark(x).block_until_ready()


def traced_window(load, seconds: float, trace_dir: Path, mark):
    """The window under the profiler, device only, between two marker
    runs. Returns the end-to-end figures and the host times just after
    the first marker finished and just before the last was sent."""
    import jax

    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 0
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(trace_dir), profiler_options=opts):
        mark()
        opened = CLOCK()
        e2e = load.window(seconds)
        closed = CLOCK()
        mark()
    return e2e, (opened, closed)


def report_checks(checks: dict) -> None:
    """The compared numbers beside their limits: the last lines on
    standard error."""
    for k, c in checks.items():
        ok = "ok" if c["value"] <= c["limit"] else "OVER"
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r}) {ok}",
              file=sys.stderr, flush=True)
