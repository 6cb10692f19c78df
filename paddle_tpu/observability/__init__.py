"""paddle_tpu.observability — unified telemetry layer.

One switch (`enable()`) threads structured telemetry through the stack:

  * ops/dispatch.call       → per-op invocation counters, AMP casts
                              inserted, pallas-override hits (zero-cost
                              when disabled: a single module-flag check)
  * jit entry points        → compile events with wall time + recompile
                              cause diagnosis (compile_tracker)
  * distributed/collective  → per-collective call/byte counters keyed by
                              op and mesh axis + host spans
  * io/shm_loader           → queue-depth gauge, batch-wait histogram
  * profiler.RecordEvent    → host spans merged into the Chrome trace

Everything lands in the metrics registry (JSON-lines / Prometheus text,
see metrics.py) and the host trace buffer (chrome://tracing JSON, see
trace.py).  `hapi.callbacks.MetricsLogger` drives this from Model.fit.

Counting happens at Python dispatch time: inside a jitted program ops and
collectives are counted once per TRACE (compilation), not once per device
execution — pair with the device xplane trace for on-device timing.
"""
from __future__ import annotations

import collections
import contextlib

from . import metrics  # noqa: F401
from . import trace  # noqa: F401
from . import compile_tracker  # noqa: F401
from .metrics import MetricsRegistry, registry  # noqa: F401
from .trace import chrome_trace, export_chrome_trace  # noqa: F401
from .compile_tracker import RecompileWarning  # noqa: F401

__all__ = ["enable", "disable", "enabled", "reset", "dispatch_stats",
           "registry", "MetricsRegistry", "span", "chrome_trace",
           "export_chrome_trace", "RecompileWarning", "metrics", "trace",
           "compile_tracker"]

_enabled = False
_dispatch_tel = None
_comms_tel = None


def enabled() -> bool:
    return _enabled


def span(name, cat="host", args=None):
    """Record the enclosed block as one span of the recorder
    (`trace.traced`) while telemetry is enabled; a no-op otherwise.  The
    serving engine and `TrainStep` call `trace.traced` themselves and
    always record."""
    if not _enabled:
        return contextlib.nullcontext()
    return trace.traced(name, cat=cat, counts=args)


class _DispatchTelemetry:
    """Hot-path sink installed as ops.dispatch._TELEMETRY.

    Plain Counter increments only — registry materialization happens via
    the export-time collector so dispatch never pays registry lookups."""

    __slots__ = ("ops", "casts", "pallas", "_overridden")

    def __init__(self, overridden):
        self.ops = collections.Counter()
        self.casts = collections.Counter()
        self.pallas = collections.Counter()
        self._overridden = overridden   # live view of dispatch._OVERRIDDEN

    def op(self, name):
        self.ops[name] += 1
        if name in self._overridden:
            self.pallas[name] += 1

    def cast(self, op_name):
        self.casts[op_name] += 1


def _dispatch_collector(reg):
    tel = _dispatch_tel
    if tel is None:
        return
    for op, n in tel.ops.items():
        reg.counter("dispatch_calls_total", op=op)._set_total(n)
    for op, n in tel.casts.items():
        reg.counter("amp_casts_total", op=op)._set_total(n)
    for op, n in tel.pallas.items():
        reg.counter("pallas_override_hits_total", op=op)._set_total(n)


def _mesh_collector(reg):
    """Export-time mesh topology gauges: read live so they appear no
    matter whether fleet.init ran before or after enable()."""
    try:
        from ..distributed import mesh as mesh_mod
    except Exception:
        return
    if not mesh_mod.has_mesh():
        return
    for ax in ("dp", "mp", "pp", "ep"):
        reg.gauge("mesh_axis_degree", axis=ax).set(mesh_mod.degree(ax))


class _CommsTelemetry:
    """Sink installed as distributed.collective._TELEMETRY."""

    __slots__ = ("_reg",)

    def __init__(self, reg):
        self._reg = reg

    def record(self, op, nbytes, axis, t0, dur_s):
        axis = str(axis)
        self._reg.counter("comms_calls_total", op=op, axis=axis).inc()
        self._reg.counter("comms_bytes_total", op=op, axis=axis).inc(nbytes)
        self._reg.histogram("comms_seconds", op=op).observe(dur_s)
        trace.add_complete(op, "comms", t0, dur_s,
                           args={"bytes": int(nbytes), "axis": axis})


def enable(registry_=None, warn_after=None):
    """Switch telemetry on: installs the dispatch and collective hooks and
    (optionally) retargets the active registry (so EVERY instrument —
    compile tracker, loader, fleet, dy2static — writes to it) and the
    recompile-warning threshold."""
    global _enabled, _dispatch_tel, _comms_tel
    from ..ops import dispatch as _dispatch
    from ..distributed import collective as _collective
    if registry_ is not None:
        metrics.set_registry(registry_)
    reg = metrics.registry()
    if _dispatch_tel is None:
        _dispatch_tel = _DispatchTelemetry(_dispatch._OVERRIDDEN)
    _dispatch._TELEMETRY = _dispatch_tel
    reg.add_collector(_dispatch_collector)
    reg.add_collector(_mesh_collector)
    _comms_tel = _CommsTelemetry(reg)
    _collective._TELEMETRY = _comms_tel
    if warn_after is not None:
        compile_tracker.set_warn_after(warn_after)
    _enabled = True


def disable():
    """Switch telemetry off; accumulated metrics/trace data is kept until
    reset() so post-run exports still work.  A registry retargeted by
    enable(registry_=...) is released back to the process default (its
    dispatch totals are materialized first, so its snapshot stays
    complete and a later enable() cannot pollute it)."""
    global _enabled, _comms_tel
    from ..ops import dispatch as _dispatch
    from ..distributed import collective as _collective
    _dispatch._TELEMETRY = None
    _collective._TELEMETRY = None
    _comms_tel = None
    reg = metrics.registry()
    _dispatch_collector(reg)
    _mesh_collector(reg)
    reg.remove_collector(_dispatch_collector)
    reg.remove_collector(_mesh_collector)
    metrics.set_registry(None)
    _enabled = False


def dispatch_stats():
    """{'ops': {...}, 'amp_casts': {...}, 'pallas_hits': {...}} counters."""
    tel = _dispatch_tel
    if tel is None:
        return {"ops": {}, "amp_casts": {}, "pallas_hits": {}}
    return {"ops": dict(tel.ops), "amp_casts": dict(tel.casts),
            "pallas_hits": dict(tel.pallas)}


def reset():
    """Clear every telemetry store (registry, trace buffer, compile
    tracker, dispatch counters).  The enabled/disabled state is kept."""
    global _dispatch_tel
    metrics.registry().reset()
    trace.clear()
    compile_tracker.reset()
    if _dispatch_tel is not None:
        _dispatch_tel.ops.clear()
        _dispatch_tel.casts.clear()
        _dispatch_tel.pallas.clear()
