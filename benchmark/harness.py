"""What every run shares: finding a cell and its files by name, the device
check, the compile cache, the profiler session, the per-layer readers and
the result line.  Nothing here knows a model or a traffic mix."""
import importlib
import importlib.util
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmark")
OUT = os.path.join(ROOT, ".bench_out")      # traces; listed in .gitignore
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class BenchError(RuntimeError):
    """A refusal with a message for the user (exit code 2, no result)."""


def say(msg):
    print(f"# bench: {msg}", flush=True)


# ------------------------------------------------------------------ lookup
def _json(path):
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise BenchError(f"missing file {os.path.relpath(path, ROOT)}") \
            from None


def load_cell(name, root=ROOT, rehearse=False):
    """The cell `name` of BENCHMARK.json with its configuration, its mix
    and the metrics that are its to report: found by name, never by code
    that knows the cell.  `rehearse` lays the files' ``rehearsal`` sizes
    over the real ones (the CPU mode; never a result)."""
    bench = _json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    cell = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = _json(os.path.join(root, entry["file"]))
    mix = _json(os.path.join(root, "benchmark", "traffic",
                             cell["traffic"] + ".json"))
    if rehearse:
        config = {**config, **config.get("rehearsal", {})}
        mix = {**mix, **mix.get("rehearsal", {})}

    def mine(metrics):
        return [m for m in metrics
                if name in m.get("workloads", [name])]
    return {"name": name, "cell": cell, "config": config, "mix": mix,
            "end_to_end": mine(bench["end_to_end"]),
            "per_layer": mine(bench["per_layer"]),
            "run_seconds": bench["run_seconds"]}


def load_driver(kind):
    path = os.path.join(HERE, "drivers", f"{kind}.py")
    if not os.path.exists(path):
        raise BenchError(f"no driver for kind {kind!r} "
                         f"(benchmark/drivers/{kind}.py)")
    return importlib.import_module(f"benchmark.drivers.{kind}")


def resolve(dotted):
    """``package.module:attribute`` -> the attribute (a configuration
    file names its model, its config class and its loss this way)."""
    mod, _, attr = dotted.partition(":")
    return getattr(importlib.import_module(mod), attr)


def build_model(cfg):
    """The configuration's model as the program builds it (under
    LazyGuard, as chip_smoke.py does)."""
    import paddle_tpu as pt
    kw = dict(vocab_size=int(cfg["vocab_size"]),
              hidden_size=int(cfg["hidden_size"]),
              num_layers=int(cfg["num_hidden_layers"]),
              num_heads=int(cfg["num_attention_heads"]),
              intermediate_size=int(cfg["intermediate_size"]),
              max_position_embeddings=int(cfg["max_position_embeddings"]))
    kw.update(cfg.get("model_kwargs", {}))
    with pt.LazyGuard():
        return resolve(cfg["model"])(resolve(cfg["model_config"])(**kw))


def load_weights(model, ref, cfg, seed):
    """The seed's weights, made by the reference's one jitted program,
    into the program's model through its public ``set_state_dict``."""
    missing, unexpected = model.set_state_dict(
        ref.to_program(ref.init_weights(
            cfg, int(cfg["max_position_embeddings"]), seed), cfg))
    if missing or unexpected:
        raise BenchError(
            f"the reference's weights do not name the model's parameters: "
            f"missing {missing[:3]} unexpected {unexpected[:3]}")


def load_reference(dotted):
    """``benchmark.references.gpt`` -> module."""
    if not dotted.startswith("benchmark.references."):
        raise BenchError(f"a reference lives under benchmark/references/, "
                         f"not at {dotted!r}")
    return importlib.import_module(dotted)


def load_reader(metric_name):
    """The reader of a per-layer metric: ``benchmark/metrics/<name>.py``,
    or, for a quantity split by suffix (``x.chat``), ``<x>.py``.  It has
    one function, ``read(run) -> number or None``."""
    for stem in (metric_name, metric_name.split(".", 1)[0]):
        path = os.path.join(HERE, "metrics", stem + ".py")
        if os.path.exists(path):
            spec = importlib.util.spec_from_file_location(
                "benchmark.metrics." + stem.replace(".", "_"), path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod.read
    raise BenchError(f"no reader for per-layer metric {metric_name!r} "
                     f"under benchmark/metrics/")


def read_per_layer(spec, run):
    """{name: {"value", "unit"}} of the cell's per-layer metrics.  A
    reader that finds nothing to read returns None and is left out."""
    out = {}
    for m in spec["per_layer"]:
        value = load_reader(m["name"])(dict(run, metric=m["name"]))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


# ------------------------------------------------------------------ device
def place_cache():
    """JAX's persistent compilation cache at a fixed place inside the
    checkout (or where JAX_COMPILATION_CACHE_DIR says), through the
    program's own entry-point call, and with no lower limit on what is
    worth caching: the sub-second programs count too."""
    import jax
    from paddle_tpu.jit import compile_cache
    d = compile_cache.place_jax_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return d


def require_devices(chips, rehearse):
    """jax.devices() as a description; refuses anything but a TPU with
    at least `chips` chips unless this is a named CPU rehearsal."""
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if rehearse:
        return info
    if info["platform"] != "tpu":
        raise BenchError(f"no accelerator: JAX found {info['platform']}")
    if len(devs) < chips:
        raise BenchError(f"the cell asks for {chips} chips, JAX found "
                         f"{len(devs)}")
    return info


def memory_peak_bytes(chips):
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks))


class CompileCounter:
    """Counts XLA compilations (cache loads included) between open() and
    close() through JAX's own monitoring events: there must be none
    inside a measured window."""

    def __init__(self):
        self.n = 0
        self._on = False

    def _listen(self, event, duration, **kw):
        if self._on and event == COMPILE_EVENT:
            self.n += 1

    def open(self):
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(self._listen)
        self._on = True

    def close(self):
        self._on = False
        return self.n


# ---------------------------------------------------------------- profiler
# host spans kept from a trace: the harness's own, then the program's
# (each `traced()` block of `LLMEngine.step` is also an annotation of the
# same name), so that an idle gap is labelled by the innermost phase
SPANS = ("train.step", "train.feed", "train.read_loss", "engine.step",
         "arrivals", "idle.wait",
         "serving.step", "serving.schedule", "serving.prefill",
         "serving.decode.prepare", "serving.decode.dispatch",
         "serving.decode.wait", "serving.decode.fetch", "serving.sample")
# the runtime's own host events around a program, kept beside the spans
# for a reader to pin the device clock's shift with (PERF.md section 7);
# they label no gap
RUNTIME_EVENTS = ("tpu::System::Execute=>IssueSequencedEvent",
                  "ReadSyncFlag")


def span(name):
    """A host span on the profiler's clock (a no-op when no trace runs)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


class Profiler:
    """One profiler session of a few steps or seconds, written under
    .bench_out/ in the checkout and removed after it is read."""

    def __init__(self, workload):
        self.dir = os.path.join(OUT, "trace-" + workload)
        self.start_s = self.stop_s = None

    def start(self):
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        t = time.perf_counter()
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.start_s = time.perf_counter() - t

    def stop(self):
        import jax
        t = time.perf_counter()
        jax.profiler.stop_trace()
        self.stop_s = time.perf_counter() - t

    def read(self):
        from benchmark import trace
        out = trace.load(self.dir, SPANS + RUNTIME_EVENTS)
        shutil.rmtree(self.dir, ignore_errors=True)
        return out


def reduce_trace(tr, chips):
    """(device dict additions, breakdown, clipped trace) of a session:
    busy seconds averaged over the chips used, the traced window, the
    costliest device operations and the longest idle gaps of device 0
    labelled by the harness span that covered them."""
    from benchmark import trace
    t0, t1 = trace.window_of(tr)
    used = sorted(tr["devices"])[:chips]
    busy = [trace.busy_ns(tr["devices"][d]["ops"]) for d in used]
    ops0 = tr["devices"][used[0]]["ops"]
    breakdown = {
        "device_ops": trace.top_ops(ops0, 10),
        "idle_gaps": trace.label_gaps(
            trace.idle_gaps(ops0, t0, t1),
            [s for s in tr["spans"] if s[0] in SPANS], 10)}
    return ({"busy_s": sum(busy) / len(busy) / 1e9,
             "window_s": (t1 - t0) / 1e9}, breakdown)


# ------------------------------------------------------------------ result
def within(value, limit):
    """A compared number passes when it is a number at or under its
    limit (NaN and None never pass)."""
    return value is not None and value == value and value <= limit


def verdict(checks):
    """`checks` is [(name, value, limit)]: correct when every value is
    within its limit."""
    return all(within(v, lim) for _, v, lim in checks)


def result_line(spec, trace_on, device, e2e, per_layer, attempted, failed,
                checks, breakdown=None, extra=None):
    """The one JSON object that ends standard output, each number
    compared beside its limit under ``compared``, which comes last.
    `extra` holds further keys the driver ignores (the pool's use)."""
    correct = verdict(checks)
    metrics = per_layer if trace_on else {
        m["name"]: {"value": float(e2e[m["name"]]), "unit": m["unit"]}
        for m in spec["end_to_end"] if e2e.get(m["name"]) is not None}
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": device}
    if trace_on and breakdown:
        line["breakdown"] = breakdown
    line.update(extra or {})
    line["compared"] = {n: {"value": v, "limit": lim}
                        for n, v, lim in checks}
    for n, v, lim in checks:
        print(f"compared {n}: value {v} limit {lim} "
              f"{'ok' if within(v, lim) else 'OVER'}", file=sys.stderr)
    sys.stderr.flush()
    return json.dumps(line)
