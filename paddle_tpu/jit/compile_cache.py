"""Persistent compilation cache — cold-start hardening for every jit entry.

At fleet scale every restart (supervisor backoff, heartbeat hang-kill,
elastic mesh change) pays full `jax.jit` trace+lower+compile from scratch;
after PR-5/6 made restarts cheap to *trigger*, compilation became the
dominant recovery cost.  This module makes it a disk read: the first
process to compile a program serializes the XLA executable
(`jax.experimental.serialize_executable`) into an on-disk store, and every
later process — a restarted worker, a concurrent rank under
`distributed/launch`, a serving replica — loads it back in milliseconds.

Keying mirrors the compile-tracker registry: function identity (label +
source hashes of the user code that shapes the program), the abstract
call signature (shape/dtype/weak-type per leaf + pytree structure),
static arguments, the mesh fingerprint, and the jax/jaxlib/backend
versions.  Any mismatch is simply a miss — a stale entry can never be
served to a different program.

Robustness-first storage contract:

  * writes are crash-safe: payload lands in a same-directory temp file
    and is published with one atomic ``os.replace`` — a torn write is
    never observable under the final name;
  * every entry carries a sha256 content checksum; a corrupt or
    truncated entry is moved to ``quarantine/`` and treated as a miss
    (silent recompile), never a crash;
  * sharing is lock-free: concurrent workers race benignly (last
    publisher wins, both payloads are byte-identical by construction);
    no lock files, so no stale-lock deadlock after a kill -9;
  * the store is size-budgeted (``PADDLE_TPU_CACHE_MAX_BYTES``):
    oldest-first GC after each put, never collecting the entry just
    published; a reader losing the race to GC sees a plain miss;
  * an unwritable/full directory degrades to in-memory-only with ONE
    warning — the training loop never aborts because of the cache.

Fault sites (resilience/chaos.py): ``cache.corrupt`` flips bytes in the
just-published entry, ``cache.race`` publishes a competing write first,
``cache.evict_inflight`` GCs the entry immediately after publish.  The
``tools/chaos_check.py --cold-start`` drill asserts warm restarts do
zero recompiles with bit-exact loss continuity and corrupt entries are
quarantined transparently.

Donated executables are not serialized directly.  On jaxlib 0.4.36/CPU
a deserialized executable whose program bakes input/output buffer
aliases (``donate_argnums``) corrupted memory at run or teardown time —
a nondeterministic segfault in ~40% of warm restarts.  Entries that
donate (TrainStep, DistributedTrainStep) therefore publish an alias-free
TWIN compilation (`plain_jit` in `FunctionCache.lookup`): donation never
changes the math, only buffer reuse, so a restarted process loads a
bit-exact executable while the compiling process keeps its donating
one.  The twin doubles compile cost on the publishing miss only.
Re-tested on jax/jaxlib 0.9.0, CPU backend (PR 21): 31 warm restarts in
fresh subprocesses loading the DONATING executable
(``PADDLE_TPU_CACHE_DONATED=1``), 0 crashes, losses bit-exact — the
hazard no longer reproduces there.  It has not been re-tested on the TPU
backend, where a crash costs a machine, so the twin stays until it is
(ROADMAP D-queue); ``PADDLE_TPU_CACHE_DONATED=1`` serializes the
donating executable directly.

Env knobs: ``PADDLE_TPU_CACHE_DIR`` (unset = disabled),
``PADDLE_TPU_CACHE_MAX_BYTES`` (default 2 GiB),
``PADDLE_TPU_CACHE_DONATED=1`` (trust donated round-trips).
"""
from __future__ import annotations

import hashlib
import inspect
import json
import os
import pickle
import threading
import time
import warnings

import jax
from jax.experimental import serialize_executable as _se

_ENV_DIR = "PADDLE_TPU_CACHE_DIR"
_ENV_MAX = "PADDLE_TPU_CACHE_MAX_BYTES"
_ENV_DONATED = "PADDLE_TPU_CACHE_DONATED"
_MAGIC = b"PTCC0001"
_SUFFIX = ".ccx"
_DEFAULT_MAX_BYTES = 2 << 30


class CacheUnavailableWarning(UserWarning):
    """The persistent cache degraded to in-memory-only (unwritable/full
    directory)."""


def _reg():
    from ..observability import metrics
    return metrics.registry()


def serialize_compiled(compiled):
    """Pickle-ready form of a compiled executable: jax's serialized
    payload plus the ids of the devices it was compiled for."""
    blob, in_tree, out_tree = _se.serialize(compiled)
    ids = [d.id for d in compiled.runtime_executable().local_devices()]
    return blob, in_tree, out_tree, ids


def load_compiled(payload):
    """The inverse of `serialize_compiled`.  The executable is loaded
    onto the devices it was compiled for: left to its default,
    `deserialize_and_load` loads onto EVERY device of the backend, and
    a one-device program then refuses its arguments on a multi-device
    host ("expected ... to have N shards, got: [1]")."""
    blob, in_tree, out_tree, ids = payload
    by_id = {d.id: d for d in jax.devices()}
    return _se.deserialize_and_load(
        blob, in_tree, out_tree,
        execution_devices=[by_id[i] for i in ids])


# ===================================================================
# JAX's own persistent compilation cache — placed from outside
# ===================================================================
_JAX_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def place_jax_cache():
    """Switch on JAX's persistent compilation cache for this process and
    return its directory.  Called by process entry points (chip_smoke.py,
    tools/serve.py, the serving worker, init_parallel_env) — never at
    import.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps its
    cache there and nothing here sets another.  Where it is not, the
    cache lives at ``<checkout>/.jax_cache``: a fixed path, because the
    next process must find it again — a temporary name, a pid or a time
    in it would never hit.  The variable is exported so that child
    processes land in the same directory.
    This is independent of the executable store above
    (``PADDLE_TPU_CACHE_DIR``)."""
    d = os.environ.get(_JAX_CACHE_ENV)
    if not d:
        d = os.path.join(_CHECKOUT, ".jax_cache")
        os.environ[_JAX_CACHE_ENV] = d
        jax.config.update("jax_compilation_cache_dir", d)
    return d


def jax_cache_entries(d):
    """Number of entries in a JAX compilation cache directory."""
    try:
        return sum(1 for n in os.listdir(d) if n.endswith("-cache"))
    except OSError:
        return 0


# ===================================================================
# fingerprints — what makes two compilations "the same program"
# ===================================================================
_ENV_FP = None


def env_fingerprint():
    """Backend identity: an executable only replays on the stack that
    built it (jax/jaxlib version, platform, device kind and count).
    Computed once — the backend cannot change within a process, and
    `jax.devices()` is too slow for a per-step digest."""
    global _ENV_FP
    if _ENV_FP is not None:
        return _ENV_FP
    try:
        import jaxlib
        jl = getattr(jaxlib, "__version__", "?")
    except Exception:  # pragma: no cover
        jl = "?"
    try:
        devs = jax.devices()
        plat, kind, n = devs[0].platform, devs[0].device_kind, len(devs)
    except Exception:  # pragma: no cover - backend init failure
        plat, kind, n = "?", "?", 0
    _ENV_FP = (jax.__version__, jl, plat, kind, n)
    return _ENV_FP


def mesh_fingerprint():
    """Axis names + degrees of the active fleet mesh ('' when none):
    sharded executables are only valid on the topology they compiled
    for, so the mesh is part of the key."""
    try:
        from ..distributed import mesh as mesh_mod
        if not mesh_mod.has_mesh():
            return ""
        m = mesh_mod.get_mesh()
        return repr(tuple(zip(m.axis_names, m.devices.shape)))
    except Exception:  # pragma: no cover
        return ""


def fingerprint_callables(*objs):
    """Best-effort identity hash of the user code shaping a program:
    source text when retrievable, else the qualified name.  A code edit
    that changes the traced computation changes the key (stale-executable
    hazard); an unobtainable source degrades to name-only keying."""
    h = hashlib.sha256()
    for o in objs:
        if o is None:
            h.update(b"<none>")
            continue
        if isinstance(o, str):
            h.update(o.encode())
            continue
        target = o
        if isinstance(o, type):
            target = getattr(o, "forward", None) or o
        try:
            h.update(inspect.getsource(target).encode())
        except (OSError, TypeError):
            h.update(repr(getattr(o, "__qualname__",
                                  getattr(o, "__name__", o))).encode())
    return h.hexdigest()


def _simple(v):
    return isinstance(v, (bool, int, float, str, type(None)))


# mutable RUNTIME state, not configuration: these advance during
# training (and land restored from a checkpoint), so a warm restart
# would never key back to the executable the cold run published
_FP_SKIP = {"_step_count", "_state", "_jitted", "last_epoch",
            "_last_lr", "training"}


def config_fingerprint(*objs):
    """repr of the simple-valued instance state of `objs` — the
    hyperparameters a traced program bakes in as CONSTANTS (optimizer
    momentum/epsilon/weight decay, model-config dropout rates, guard
    mode).  `fingerprint_callables` sees only the code: two
    ``Momentum(momentum=0.9)`` and ``Momentum(momentum=0.5)`` share
    source but must never share executables.  Object-valued attributes
    (grad clips, schedulers) contribute their type plus their own
    simple attrs, one level deep; tensors/params/callables are skipped
    (shapes are keyed by `abstract_signature`, code by
    `fingerprint_callables`)."""
    def flat(o, depth):
        if o is None:
            return "<none>"
        if _simple(o):
            return repr(o)
        d = getattr(o, "__dict__", None)
        if not isinstance(d, dict) or depth <= 0:
            return type(o).__name__
        items = []
        for k in sorted(d):
            v = d[k]
            if k in _FP_SKIP:
                continue
            if _simple(v):
                items.append(f"{k}={v!r}")
            elif isinstance(v, (tuple, list)) and all(_simple(x)
                                                      for x in v):
                items.append(f"{k}={list(v)!r}")
            elif isinstance(v, dict):   # strategy config dicts
                items.append(
                    f"{k}={{{','.join(f'{dk!r}:{dv!r}' for dk, dv in sorted(v.items(), key=lambda i: str(i[0])) if _simple(dv))}}}")
            elif getattr(v, "__dict__", None) is not None \
                    and not callable(v):
                items.append(f"{k}={flat(v, depth - 1)}")
        return f"{type(o).__name__}({','.join(items)})"
    return "|".join(flat(o, 2) for o in objs)


def abstract_signature(args):
    """(leaf avals, tree structure) of a full argument tuple — the
    shape/dtype/weak-type half of the key.  Unlike the compile tracker's
    `signature_of` this flattens nested pytrees (optimizer state), and
    the treedef repr pins the container structure an executable's
    pickled in_tree expects."""
    leaves, treedef = jax.tree_util.tree_flatten(args)
    sig = []
    for l in leaves:
        sig.append((tuple(getattr(l, "shape", ())),
                    str(getattr(l, "dtype", type(l).__name__)),
                    bool(getattr(l, "weak_type", False))))
    return tuple(sig), repr(treedef)


# ===================================================================
# the on-disk store
# ===================================================================
class CompileCache:
    """Content-addressed executable store under one directory.

    Entry format (single file ``<digest>.ccx``):
        magic(8) | header_len(8, big-endian) | header json | payload
    The header records the payload sha256/length plus human-readable key
    metadata; validation failure of any part quarantines the entry.
    """

    def __init__(self, cache_dir, max_bytes=None):
        self.dir = os.path.abspath(cache_dir) if cache_dir else None
        self.max_bytes = (_DEFAULT_MAX_BYTES if max_bytes is None
                          else int(max_bytes))
        self._mem = {}           # digest -> payload (fallback store)
        self._disk_ok = self.dir is not None
        self._warned = False
        self._lock = threading.Lock()

    # ------------------------------------------------------------ paths
    def _path(self, digest):
        return os.path.join(self.dir, digest + _SUFFIX)

    def _degrade(self, why):
        """Switch to in-memory-only, warning exactly once."""
        self._disk_ok = False
        with self._lock:
            if self._warned:
                return
            self._warned = True
        warnings.warn(
            f"persistent compile cache degraded to in-memory-only: {why} "
            f"(dir={self.dir!r}); restarts of this process will recompile "
            f"from scratch", CacheUnavailableWarning, stacklevel=4)
        _reg().counter("compile_cache_degraded_total").inc()

    def _quarantine(self, path, why):
        """Move a damaged entry out of the lookup namespace (atomic, so
        concurrent readers either see the old entry or a miss, never a
        half-moved file)."""
        qdir = os.path.join(self.dir, "quarantine")
        try:
            os.makedirs(qdir, exist_ok=True)
            dst = os.path.join(
                qdir, f"{os.path.basename(path)}.{os.getpid()}."
                      f"{int(time.time() * 1e3)}")
            os.replace(path, dst)
        except FileNotFoundError:
            return  # another process quarantined/evicted it first
        except OSError:
            try:
                os.unlink(path)
            except OSError:
                pass
        else:
            self._prune_quarantine(qdir)
        _reg().counter("compile_cache_quarantined_total").inc()
        warnings.warn(
            f"quarantined corrupt compile-cache entry "
            f"{os.path.basename(path)} ({why}); recompiling",
            CacheUnavailableWarning, stacklevel=5)

    _QUARANTINE_KEEP = 16

    @staticmethod
    def _prune_quarantine(qdir):
        """Quarantined files are post-mortem evidence, not cache
        entries: keep only the newest few so repeated corruption (flaky
        storage, preemption-torn writes) can't grow the directory
        outside the size budget forever."""
        try:
            names = sorted(os.listdir(qdir))
        except OSError:
            return
        # names end in .<pid>.<millis>: lexical sort is not age order —
        # stat for mtime, tolerate concurrent pruners
        aged = []
        for n in names:
            try:
                aged.append((os.path.getmtime(os.path.join(qdir, n)), n))
            except OSError:
                continue
        aged.sort()
        for _, n in aged[:-CompileCache._QUARANTINE_KEEP]:
            try:
                os.unlink(os.path.join(qdir, n))
            except OSError:
                continue

    # -------------------------------------------------------------- get
    def get(self, digest):
        """Payload bytes for `digest`, or None (miss).  Any validation
        failure quarantines the entry and reports a miss."""
        if not self._disk_ok:
            return self._mem.get(digest)
        path = self._path(digest)
        try:
            with open(path, "rb") as f:
                raw = f.read()
        except FileNotFoundError:
            return None
        except OSError as e:
            self._degrade(f"read failed: {e}")
            return self._mem.get(digest)
        try:
            if raw[:8] != _MAGIC:
                raise ValueError("bad magic")
            hlen = int.from_bytes(raw[8:16], "big")
            header = json.loads(raw[16:16 + hlen])
            payload = raw[16 + hlen:]
            if len(payload) != header["payload_len"]:
                raise ValueError(
                    f"torn payload ({len(payload)} of "
                    f"{header['payload_len']} bytes)")
            if hashlib.sha256(payload).hexdigest() != header["sha256"]:
                raise ValueError("checksum mismatch")
        except (ValueError, KeyError, IndexError, json.JSONDecodeError,
                UnicodeDecodeError) as e:
            self._quarantine(path, str(e))
            return None
        _reg().counter("compile_cache_read_bytes_total").inc(len(raw))
        return payload

    # -------------------------------------------------------------- put
    def put(self, digest, payload, meta=None):
        """Publish `payload` under `digest` (crash-safe, lock-free)."""
        from ..resilience import chaos as _chaos
        if not self._disk_ok:
            self._mem[digest] = payload
            return
        header = dict(meta or {})
        header.update(sha256=hashlib.sha256(payload).hexdigest(),
                      payload_len=len(payload),
                      created=time.time())
        hjson = json.dumps(header, sort_keys=True).encode()
        blob = _MAGIC + len(hjson).to_bytes(8, "big") + hjson + payload
        path = self._path(digest)
        # chaos: a competing worker publishes first — ours must replace
        # it atomically (last-writer-wins; payloads are byte-identical
        # in real races, a *different* competing blob is still a valid
        # entry because publication is all-or-nothing)
        if _chaos._PLAN is not None and _chaos.fire("cache.race"):
            self._write_atomic(path, blob)
        try:
            self._write_atomic(path, blob)
        except OSError as e:
            self._degrade(f"write failed: {e}")
            self._mem[digest] = payload
            return
        _reg().counter("compile_cache_puts_total").inc()
        _reg().counter("compile_cache_written_bytes_total").inc(len(blob))
        if _chaos._PLAN is not None and _chaos.fire("cache.corrupt"):
            self._flip_bytes(path)
        if _chaos._PLAN is not None and _chaos.fire("cache.evict_inflight"):
            # GC raced the publish and collected the fresh entry: the
            # next reader must see a clean miss, not a torn file
            try:
                os.unlink(path)
            except OSError:
                pass
            _reg().counter("compile_cache_evictions_total").inc()
        else:
            self.gc(protect=digest)

    def _write_atomic(self, path, blob):
        tmp = (f"{path}.{os.getpid()}.{threading.get_ident()}.tmp")
        try:
            with open(tmp, "wb") as f:
                f.write(blob)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)
        finally:
            try:
                os.unlink(tmp)
            except OSError:
                pass

    @staticmethod
    def _flip_bytes(path):
        """The cache.corrupt fault: damage the published payload so a
        later get() must quarantine instead of deserializing garbage."""
        try:
            with open(path, "r+b") as f:
                f.seek(-16, os.SEEK_END)
                f.write(b"\xff" * 8)
        except OSError:
            pass

    # --------------------------------------------------------------- gc
    def entries(self):
        """[(path, mtime, size)] of live entries, oldest first."""
        try:
            names = os.listdir(self.dir)
        except OSError:
            return []
        out = []
        for n in names:
            if not n.endswith(_SUFFIX):
                continue
            p = os.path.join(self.dir, n)
            try:
                st = os.stat(p)
            except OSError:
                continue  # lost a race to GC/quarantine in another proc
            out.append((p, st.st_mtime, st.st_size))
        out.sort(key=lambda t: t[1])
        return out

    def total_bytes(self):
        return sum(s for _, _, s in self.entries())

    def gc(self, protect=None):
        """Evict oldest entries until the store fits the byte budget.
        `protect` (a digest) is never collected — the entry just
        published must survive its own GC pass."""
        ents = self.entries()
        total = sum(s for _, _, s in ents)
        _reg().gauge("compile_cache_bytes").set(total)
        if total <= self.max_bytes:
            return 0
        keep = self._path(protect) if protect else None
        evicted = 0
        for path, _, size in ents:
            if total <= self.max_bytes:
                break
            if path == keep:
                continue
            try:
                os.unlink(path)
            except OSError:
                continue  # concurrent evictor got it; its size is gone
            total -= size
            evicted += 1
        if evicted:
            _reg().counter("compile_cache_evictions_total").inc(evicted)
            _reg().gauge("compile_cache_bytes").set(max(total, 0))
        return evicted


# ===================================================================
# process-level switch
# ===================================================================
_CACHE = None
_CONFIGURED = False
_LOCK = threading.Lock()


def configure(cache_dir=None, max_bytes=None):
    """Install the process cache (None disables).  Overrides the env
    knobs; returns the active CompileCache or None."""
    global _CACHE, _CONFIGURED
    with _LOCK:
        if cache_dir is None:
            _CACHE = None
        else:
            try:
                os.makedirs(cache_dir, exist_ok=True)
                probe_ok = os.access(cache_dir, os.W_OK)
            except OSError:
                probe_ok = False
            _CACHE = CompileCache(cache_dir, max_bytes=max_bytes)
            if not probe_ok:
                _CACHE._degrade("directory is not writable")
        _CONFIGURED = True
    return _CACHE


def cache():
    """The active CompileCache (auto-configured from PADDLE_TPU_CACHE_DIR
    on first use), or None when the cache is disabled."""
    global _CONFIGURED
    if not _CONFIGURED:
        d = os.environ.get(_ENV_DIR)
        mb = os.environ.get(_ENV_MAX)
        configure(d if d else None,
                  max_bytes=int(mb) if mb else None)
    return _CACHE


def enabled():
    return cache() is not None


def reset():
    """Drop the process cache state (tests); env is re-read on next use.

    Deliberately KEEPS the executable memo: purging it would let this
    process deserialize a second live instance of an executable it
    already holds — the jaxlib double-instance hazard `_MEMO` exists to
    prevent (see its comment).  Use `_drop_memo_unsafe` in a test only
    when the process provably never compiled the entries it will load.
    """
    global _CACHE, _CONFIGURED
    with _LOCK:
        _CACHE = None
        _CONFIGURED = False


def _drop_memo_unsafe():
    """Tests only — forget live executables (see reset's warning)."""
    with _MEMO_LOCK:
        _MEMO.clear()


# ===================================================================
# per-jit-entry frontend
# ===================================================================
# Process-global memo of live executables, keyed by digest.  Beyond
# dedup (a TrainStep re-created after an in-process rollback reuses the
# executable instead of re-reading disk), this is a CRASH GUARD: on
# jaxlib 0.4.36/CPU, deserializing a second live instance of an
# executable this process already compiled segfaulted nondeterministically
# (double-instance buffer-alias corruption; a fresh process loading the
# same entry is stable; not re-tested on 0.9).  The memo guarantees one live instance per
# program per process, so the persistent path only ever deserializes in
# a process that never compiled that program — exactly the restart case
# it exists for.
_MEMO = {}           # digest -> (runner_or_compiled, extra)
_MEMO_LOCK = threading.Lock()


class _LoadedRunner:
    """A deserialized executable with a one-shot fallback: if this
    process calls it with an incompatible argument structure (the key
    matched but e.g. a container type drifted), the call falls back to
    the live jitted function — degradation, never an abort.  The
    signature check happens before dispatch, so donated buffers are
    still alive on the fallback path."""

    __slots__ = ("compiled", "jitted", "label", "broken")

    def __init__(self, compiled, jitted, label):
        self.compiled = compiled
        self.jitted = jitted
        self.label = label
        self.broken = False

    def __call__(self, *args):
        if not self.broken:
            try:
                return self.compiled(*args)
            except TypeError as e:
                self.broken = True
                _reg().counter("compile_cache_incompatible_total",
                               fn=self.label).inc()
                warnings.warn(
                    f"cached executable for {self.label} rejected the "
                    f"live call signature ({e}); recompiling",
                    CacheUnavailableWarning, stacklevel=2)
        return self.jitted(*args)


class FunctionCache:
    """Frontend one jit entry point holds: per-signature digesting, an
    in-process memo of live executables, and the load-or-compile flow.

    `fingerprint` is a tuple of callables/strings identifying the user
    code this entry compiles (model forward, loss fn, optimizer class);
    hashed once at construction.
    """

    def __init__(self, label, fingerprint=()):
        self.label = label
        self._fp = fingerprint_callables(*fingerprint)

    def digest(self, args, static=()):
        sig, tree = abstract_signature(args)
        h = hashlib.sha256()
        for part in (self.label, self._fp, repr(sig), tree,
                     repr(tuple(repr(s) for s in static)),
                     repr(env_fingerprint()), mesh_fingerprint()):
            h.update(part.encode())
            h.update(b"\x00")
        return h.hexdigest()

    def lookup(self, jitted, args, static=(), extra_fn=None,
               plain_jit=None):
        """Resolve a runner for this call.

        Returns (runner, outcome, extra): runner(*args) executes the
        program; outcome is 'mem' (already live in this process), 'hit'
        (loaded from the persistent store), 'miss' (compiled now and
        published), or 'bypass' (cache unusable for this program — plain
        jit call).  `extra_fn` supplies a pickleable side value captured
        AFTER a miss compiles (e.g. an output treedef discovered during
        tracing); it is stored with the entry and returned on 'hit' so a
        warm restart recovers trace-time metadata without tracing.

        Entries whose `jitted` donates buffers MUST pass `plain_jit` — a
        zero-arg callable returning a donation-free jit of the same
        function.  A miss then publishes the alias-free twin compilation
        instead of the donating executable (deserialized donated
        executables segfault on this jaxlib — see the module docstring);
        the donating executable still serves this process.
        """
        c = cache()
        if c is None:
            return jitted, "bypass", None
        digest = self.digest(args, static)
        with _MEMO_LOCK:
            hit = _MEMO.get(digest)
        if hit is not None:
            return hit[0], "mem", hit[1]
        blob = c.get(digest)
        if blob is not None:
            t0 = time.perf_counter()
            try:
                exe, extra = pickle.loads(blob)
                compiled = load_compiled(exe)
            except Exception as e:
                # payload passed the checksum but won't load (e.g. an
                # XLA-internal format change): quarantine + recompile
                if c._disk_ok:
                    c._quarantine(c._path(digest), f"deserialize: {e}")
                else:
                    c._mem.pop(digest, None)
            else:
                dt = time.perf_counter() - t0
                runner = _LoadedRunner(compiled, jitted, self.label)
                with _MEMO_LOCK:
                    _MEMO[digest] = (runner, extra)
                _reg().counter("compile_cache_hits_total",
                               fn=self.label).inc()
                _reg().histogram("compile_cache_load_seconds",
                                 fn=self.label).observe(dt)
                self._trace("cache-load", t0, dt)
                return runner, "hit", extra
        # ---- miss: AOT-compile so the executable can be serialized
        t0 = time.perf_counter()
        try:
            compiled = jitted.lower(*args).compile()
        except Exception:
            # a program the AOT path can't lower (or transient backend
            # failure): let the normal jit path surface/handle it
            _reg().counter("compile_cache_errors_total",
                           fn=self.label).inc()
            return jitted, "bypass", None
        dt = time.perf_counter() - t0
        extra = extra_fn() if extra_fn is not None else None
        try:
            to_publish = compiled
            if (plain_jit is not None
                    and os.environ.get(_ENV_DONATED) != "1"):
                # alias-free twin for the store: what a restarted
                # process deserializes must carry no donation
                tw0 = time.perf_counter()
                to_publish = plain_jit().lower(*args).compile()
                _reg().counter("compile_cache_twin_compiles_total",
                               fn=self.label).inc()
                _reg().histogram("compile_cache_twin_compile_seconds",
                                 fn=self.label).observe(
                                     time.perf_counter() - tw0)
            payload = pickle.dumps((serialize_compiled(to_publish), extra))
            c.put(digest, payload,
                  meta={"label": self.label, "jax": jax.__version__,
                        "mesh": mesh_fingerprint()})
        except Exception as e:
            # unserializable executable (backend quirk): still run the
            # fresh compilation; only persistence is lost
            _reg().counter("compile_cache_errors_total",
                           fn=self.label).inc()
            warnings.warn(
                f"could not persist compiled executable for "
                f"{self.label}: {e}", CacheUnavailableWarning,
                stacklevel=3)
        with _MEMO_LOCK:
            _MEMO[digest] = (compiled, extra)
        _reg().counter("compile_cache_misses_total", fn=self.label).inc()
        _reg().histogram("compile_cache_compile_seconds",
                         fn=self.label).observe(dt)
        self._trace("cache-miss-compile", t0, dt)
        return compiled, "miss", extra

    def _trace(self, what, t0, dur):
        from .. import observability as _obs
        if _obs.enabled():
            _obs.trace.add_complete(f"{what}:{self.label}", "compile",
                                    t0, dur)


def stats():
    """Hit/miss/quarantine/eviction totals summed over labels — the
    cold-start drill's assertion surface."""
    out = {"hits": 0, "misses": 0, "quarantined": 0, "evictions": 0,
           "errors": 0, "incompatible": 0, "puts": 0, "degraded": 0,
           "twin_compiles": 0}
    name_map = {"compile_cache_hits_total": "hits",
                "compile_cache_twin_compiles_total": "twin_compiles",
                "compile_cache_misses_total": "misses",
                "compile_cache_quarantined_total": "quarantined",
                "compile_cache_evictions_total": "evictions",
                "compile_cache_errors_total": "errors",
                "compile_cache_incompatible_total": "incompatible",
                "compile_cache_puts_total": "puts",
                "compile_cache_degraded_total": "degraded"}
    for rec in _reg().snapshot():
        k = name_map.get(rec["name"])
        if k is not None:
            out[k] += rec.get("value", 0)
    return out
