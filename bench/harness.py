"""The parts every cell shares: the device check, the compile watch, the
profiler window, the program's model configuration and the per-layer readers."""

from __future__ import annotations

import dataclasses
import os
import shutil
import tempfile
import time
import typing
from typing import Callable, Dict, List, Optional

import jax

from bench import tracefile
from bench.common import ROOT, cell_metrics, load_metric, log


def compile_cache() -> str:
    """JAX's persistent compile cache: where JAX_COMPILATION_CACHE_DIR says,
    else a fixed directory inside the checkout (the path is part of each
    entry's key, so it never moves). Every program is kept, however quick
    its compile, so a second run compiles nothing."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def device_info(need: int) -> dict:
    """The devices as JAX reports them; anything but ``need`` TPU chips
    or more is refused (a non-zero exit, no result printed)."""
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}
    if info["platform"] != "tpu":
        raise SystemExit(f"bench: needs a TPU; JAX found platform {info['platform']!r}")
    if info["count"] < need:
        raise SystemExit(f"bench: the cell needs {need} chips; JAX found {info['count']}")
    info["count"] = need
    return info


def memory_peak_bytes(n_chips: int) -> Optional[int]:
    peaks = []
    for d in jax.devices()[:n_chips]:
        st = d.memory_stats() or {}
        if "peak_bytes_in_use" in st:
            peaks.append(int(st["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


class CompileWatch:
    """Counts backend compiles (a persistent-cache load counts too) and
    cache hits and misses, so a compile inside the window shows."""

    def __init__(self):
        self.compiles = 0
        self.compile_s = 0.0
        self.hits = 0
        self.misses = 0

        def on_event(event, **kw):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        def on_duration(event, duration, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1
                self.compile_s += duration

        jax.monitoring.register_event_listener(on_event)
        jax.monitoring.register_event_duration_secs_listener(on_duration)


def profile_options():
    """Device ops and the host's TraceAnnotation spans, without the Python
    tracer (which records every Python call and slows the host loop)."""
    o = jax.profiler.ProfileOptions()
    o.python_tracer_level = 0
    o.host_tracer_level = 1
    return o


class Profiler:
    """Traces the whole window: ``begin()`` starts the profiler before the
    window opens, ``open()``/``close()`` (or ``poll(now)`` between steps,
    at ``start`` and ``stop``) bound the ``bench.window`` span, and
    ``finish()`` stops the profiler after the drain and reads the trace
    back. Starting and stopping the profiler stall the host, so neither
    happens inside the window."""

    def __init__(self, enabled: bool, clock: Callable[[], float]):
        self.enabled = enabled
        self.clock = clock
        self.start_at = self.stop_at = None
        self.span = None  # host clock (t0, t1) of the bench.window span
        self._dir = None
        self._ann = None
        self.events: Optional[List[tracefile.Event]] = None

    def begin(self, start: float = 0.0, stop: float = float("inf")) -> None:
        self.start_at, self.stop_at = start, stop
        if self.enabled:
            self._dir = tempfile.mkdtemp(prefix="bench-trace-")
            jax.profiler.start_trace(self._dir, profiler_options=profile_options())

    def poll(self, now: float) -> None:
        if self._dir is None:
            return
        if self._ann is None and self.span is None and now >= self.start_at:
            self.open()
        elif self._ann is not None and now >= self.stop_at:
            self.close()

    def open(self) -> None:
        if self._dir is not None:
            self._ann = jax.profiler.TraceAnnotation(tracefile.WINDOW_SPAN)
            self._ann.__enter__()
            self.span = (self.clock(), None)

    def close(self) -> None:
        if self._ann is not None:
            self.span = (self.span[0], self.clock())
            self._ann.__exit__(None, None, None)
            self._ann = None

    def finish(self) -> None:
        if self._dir is None:
            return
        self.close()
        jax.profiler.stop_trace()
        t0 = time.perf_counter()
        self.events = tracefile.load(self._dir)
        shutil.rmtree(self._dir, ignore_errors=True)
        log(f"[trace] {len(self.events)} events read in {time.perf_counter() - t0:.3f} s")


def model_config(model: dict, options: Optional[dict] = None):
    """The program's ModelConfig for a configuration file's ``model`` block,
    with a mix's ``model_options`` (serving switches such as attn_impl)."""
    from repro.models.config import ModelConfig

    return from_dict(ModelConfig, dict(model, **(options or {})))


def from_dict(cls, values: dict):
    """The dataclass ``cls`` from a dict: a field whose type is a dataclass,
    or an Optional one, is built from its dict in turn, as the class's own
    fields say."""
    hints = typing.get_type_hints(cls)
    kw = dict(values)
    for f in dataclasses.fields(cls):
        if isinstance(kw.get(f.name), dict):
            sub = [t for t in (hints[f.name], *typing.get_args(hints[f.name])) if dataclasses.is_dataclass(t)]
            if sub:
                kw[f.name] = from_dict(sub[0], kw[f.name])
    return cls(**kw)


def per_layer(bench: dict, cell: str, run) -> Dict[str, dict]:
    """Every per-layer metric of the cell that its reader finds something for."""
    out = {}
    for m in cell_metrics(bench, cell, "per_layer"):
        value = load_metric(m["name"])(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
