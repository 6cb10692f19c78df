"""Reduction of a JAX profiler trace (``*.xplane.pb``) to numbers.

A trace holds planes; a TPU chip is a plane ``/device:TPU:<n>`` whose line
``XLA Ops`` carries one event per device operation (start and duration in
nanoseconds) and whose line ``XLA Modules`` carries one event per
executed program.  The host is the plane ``/host:CPU``; spans that the
harness writes with ``jax.profiler.TraceAnnotation`` land on its thread
lines, on the same clock as the device events.

Everything here works on plain lists of ``(name, start_ns, dur_ns)`` so
that tests can feed it a synthetic trace.
"""
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
HOST_PLANE = "/host:CPU"


def find_xplane(trace_dir):
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


MOSAIC = 'custom_call_target="tpu_custom_call"'


def short(name):
    """An operation's event name is its whole HLO line: keep the
    instruction's name, with ``mosaic:`` before it where the line calls a
    Pallas (Mosaic) kernel -- the one thing that tells kernels from
    XLA's own operations while kernels carry no stable name."""
    head = name.split(" = ", 1)[0].lstrip("%")
    return "mosaic:" + head if MOSAIC in name else head


def load(trace_dir, span_names=()):
    """{"devices": {n: {"ops": [...], "modules": [...]}}, "spans": [...]}
    with events as (name, start_ns, dur_ns).  `spans` keeps the host
    events whose name is in `span_names`."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(find_xplane(trace_dir))
    want = set(span_names)
    out = {"devices": {}, "spans": []}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(
                    line.name)
                if key:
                    dev[key] = [(short(e.name), float(e.start_ns),
                                 float(e.duration_ns)) for e in line.events]
            out["devices"][int(m.group(1))] = dev
        elif plane.name == HOST_PLANE and want:
            for line in plane.lines:
                out["spans"] += [(e.name, float(e.start_ns),
                                  float(e.duration_ns))
                                 for e in line.events if e.name in want]
    return out


def clip(events, t0, t1):
    """Events cut to the window [t0, t1) (ns)."""
    out = []
    for name, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            out.append((name, a, b - a))
    return out


def busy_ns(events):
    """Length of the union of the events' intervals."""
    total, end = 0.0, None
    for s, e in sorted((s, s + d) for _, s, d in events):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def idle_gaps(events, t0, t1):
    """[(start_ns, dur_ns)] of the stretches of [t0, t1) no event covers."""
    gaps, cur = [], t0
    for s, e in sorted((s, s + d) for _, s, d in events):
        if s > cur:
            gaps.append((cur, min(s, t1) - cur))
        cur = max(cur, e)
        if cur >= t1:
            break
    if cur < t1:
        gaps.append((cur, t1 - cur))
    return [(s, d) for s, d in gaps if d > 0]


def named_sum_ns(events, pattern):
    """Summed duration of the events whose name matches `pattern`."""
    rx = re.compile(pattern) if isinstance(pattern, str) else pattern
    return sum(d for name, _, d in events if rx.search(name))


def top_ops(events, n=10):
    """[[name, seconds]] of the operations with the most summed time."""
    acc = {}
    for name, _, d in events:
        acc[name] = acc.get(name, 0.0) + d
    top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in top]


def label_gaps(gaps, spans, n=10):
    """The `n` longest gaps as [[label, seconds]]: the name of the host
    span that covers the gap's midpoint (the innermost, i.e. shortest),
    or ``uncovered``."""
    out = []
    for s, d in sorted(gaps, key=lambda g: -g[1])[:n]:
        mid = s + d / 2
        cover = [(sd, name) for name, ss, sd in spans
                 if ss <= mid < ss + sd]
        out.append([min(cover)[1] if cover else "uncovered", d / 1e9])
    return out


def window_of(trace):
    """(t0, t1) ns: from the first device event's start to the last one's
    end over all devices -- the traced window as the devices saw it."""
    starts, ends = [], []
    for dev in trace["devices"].values():
        for _, s, d in dev["ops"]:
            starts.append(s)
            ends.append(s + d)
    if not starts:
        raise ValueError("the trace holds no device operation")
    return min(starts), max(ends)
