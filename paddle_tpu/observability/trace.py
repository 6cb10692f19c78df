"""Host-side span recorder + Chrome-trace (chrome://tracing) export.

One clock, one buffer, one record shape.

* **Clock.**  `now_ns()` is Unix nanoseconds, the clock the JAX profiler
  stamps its xplane with (an event's `start_ns` there is relative to the
  `profile_start_time` stat of the plane ``Task Environment``).  It is
  `perf_counter_ns` plus ONE anchor taken at import, so it never steps
  back between two spans of one thread, and a time kept among a span's
  counts (a request's marks) stays on the clock of the span that holds
  it.  The anchor is not re-taken at an export: the kernel slews both
  clocks alike, so the two part only when the system clock is stepped.
* **Buffer.**  A ring of `RING` records: when it is full the OLDEST
  record goes and `dropped()` counts it.
* **Record.**  One tuple per span, `FIELDS`: name, start, end, its own
  identifier, the identifier of the span that caused it (`parent`), the
  request it belongs to (`rid`, None for a span that serves a whole
  batch), a small dict of counts, a category and the thread.

`record()` appends one tuple; `traced()` is the one context manager over
it, and also enters a `jax.profiler.TraceAnnotation`, so the same span
stands in the xplane of whoever profiles (a no-op of the profiler's own
while no session runs).  `add_complete()` is the older callers' form
(hapi, comms, compile tracker, dy2static, RecordEvent) and goes through
`record()`; `observability.span()` is `traced()` behind `enable()`.
"""
from __future__ import annotations

import collections
import itertools
import json
import os
import threading
import time

import jax

FIELDS = ("name", "t0_ns", "t1_ns", "sid", "parent", "rid", "counts",
          "cat", "tid")
# 16 records for each of 16,384 engine steps: a step's root and its six
# phases, its prefill chunks and the requests that finish in it
RING = 16 * 16_384


def _anchor() -> int:
    """Unix ns minus perf_counter ns, from the tightest of a few
    bracketed readings."""
    best = None
    for _ in range(5):
        a = time.perf_counter_ns()
        unix = time.time_ns()
        b = time.perf_counter_ns()
        if best is None or b - a < best[0]:
            best = (b - a, unix - (a + b) // 2)
    return best[1]


_ANCHOR = _anchor()
_Annotation = jax.profiler.TraceAnnotation
_lock = threading.Lock()
_ring = collections.deque(maxlen=RING)
_total = 0                      # records ever appended since clear()
_ids = itertools.count(1)
_tid_map = {}


def now_ns() -> int:
    """The recorder's clock: Unix nanoseconds, monotonic."""
    return time.perf_counter_ns() + _ANCHOR


def _tid() -> int:
    ident = threading.get_ident()
    tid = _tid_map.get(ident)
    if tid is None:
        with _lock:
            tid = _tid_map.setdefault(ident, len(_tid_map) + 1)
    return tid


def _append(rec):
    global _total
    with _lock:
        _ring.append(rec)
        _total += 1


def record(name, t0_ns, t1_ns, parent=None, rid=None, cat="host",
           counts=None):
    """Append one span [t0_ns, t1_ns] (whole ns on `now_ns()`'s clock) to
    the ring, with `counts` (a small dict, kept as given) beside it;
    returns the span's identifier."""
    sid = next(_ids)
    _append((name, t0_ns, t1_ns, sid, parent, rid, counts or {}, cat,
             _tid()))
    return sid


class traced:
    """``with traced("serving.step", parent=..., rid=...) as sp:`` records
    the block as one span and shows it to the profiler under the same
    name.  `sp.sid` is known from the start (give it to children as their
    `parent`); `sp.counts` may be filled until the block ends."""

    __slots__ = ("name", "parent", "rid", "sid", "cat", "counts", "t0_ns",
                 "_ann")

    def __init__(self, name, parent=None, rid=None, cat="host",
                 counts=None):
        self.name, self.parent, self.rid = name, parent, rid
        self.cat = cat
        self.counts = {} if counts is None else counts
        self.sid = next(_ids)

    def __enter__(self):
        self._ann = _Annotation(self.name)
        self._ann.__enter__()
        self.t0_ns = time.perf_counter_ns() + _ANCHOR
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns() + _ANCHOR
        self._ann.__exit__(*exc)
        _append((self.name, self.t0_ns, t1, self.sid, self.parent, self.rid,
                 self.counts, self.cat, _tid()))
        return False


def add_complete(name, cat, t0_perf, dur_s, args=None):
    """One span [t0, t0+dur] on this thread, from a `time.perf_counter()`
    reading and a duration in seconds (the older callers' form)."""
    t0 = int(t0_perf * 1e9) + _ANCHOR
    record(str(name), t0, t0 + int(max(0.0, dur_s) * 1e9), cat=cat,
           counts=args)


def spans(since_ns=None):
    """The ring's records as plain tuples (`FIELDS`), oldest first; with
    `since_ns`, those that end at or after it."""
    with _lock:
        recs = list(_ring)
    if since_ns is None:
        return recs
    return [r for r in recs if r[2] >= since_ns]


def mark() -> int:
    """How many records were ever appended; pass to chrome_trace /
    export_chrome_trace as `since` to export only what came after this
    point (per-run traces from a long-lived process)."""
    return _total


def dropped() -> int:
    """Records the ring has let go (the oldest ones) since clear()."""
    with _lock:
        return _total - len(_ring)


def clear():
    global _total
    with _lock:
        _ring.clear()
        _total = 0


def events(since=0):
    """The records appended after mark `since` as Chrome 'X' (complete)
    events, `ts` and `dur` in microseconds on the recorder's clock."""
    pid = os.getpid()
    with _lock:
        skip = max(0, since - (_total - len(_ring)))
        recs = list(itertools.islice(_ring, skip, None))
    out = []
    for name, t0, t1, sid, parent, rid, counts, cat, tid in recs:
        ev = {"name": name, "cat": cat, "ph": "X",
              "ts": t0 / 1e3, "dur": max(0, t1 - t0) / 1e3,
              "pid": pid, "tid": tid}
        args = dict(counts, sid=sid)
        if parent is not None:
            args["parent"] = parent
        if rid is not None:
            args["rid"] = rid
        ev["args"] = args
        out.append(ev)
    return out


def chrome_trace(since=0) -> dict:
    """The trace_event JSON object (metadata names + the events recorded
    after mark `since` — see mark())."""
    pid = os.getpid()
    evs = events(since)
    meta = [{"name": "process_name", "ph": "M", "pid": pid, "tid": 0,
             "args": {"name": "paddle_tpu host telemetry"}}]
    with _lock:
        meta += [{"name": "thread_name", "ph": "M", "pid": pid, "tid": tid,
                  "args": {"name": f"host-thread-{tid}"}}
                 for tid in sorted(_tid_map.values())]
    return {"traceEvents": meta + evs, "displayTimeUnit": "ms"}


def export_chrome_trace(path, since=0) -> str:
    """Write the merged timeline to `path`; returns the path."""
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    with open(path, "w") as f:
        json.dump(chrome_trace(since), f)
    return path
