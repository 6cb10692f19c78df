"""Multi-process DataLoader workers over the native shared-memory ring.

Reference parity: python/paddle/io/dataloader/dataloader_iter.py
(_DataLoaderIterMultiProcess) + its C++ shared-memory transport.  Design:
each worker is a **forkserver** process (never os.fork() from the parent —
forking a multithreaded, JAX-initialized process is a documented deadlock
risk) owning one SPSC ring (ring.c) mapped from a file in /dev/shm; worker
w produces batches w, w+W, w+2W, ... so the parent reads rings round-robin
and global batch order is preserved without any cross-process
coordination.  The work spec (dataset, batch iterator, collate) crosses to
the child as a cloudpickle blob, so locally-defined datasets/lambdas work
like they did under fork.  Payloads back are pickle protocol-5 blobs of
numpy pytrees; children force their own jax platform to cpu so they can
never race the parent for the TPU claim.
"""
from __future__ import annotations

import contextlib
import ctypes
import mmap
import os
import pickle
import signal
import tempfile
import threading
import time
import traceback
import warnings

import numpy as np

from . import native

_DEFAULT_RING_BYTES = 64 << 20
_WORKER_INFO = None


def _shm_dir():
    return "/dev/shm" if os.path.isdir("/dev/shm") else tempfile.gettempdir()


class WorkerInfo:
    """paddle.io.get_worker_info parity for IterableDataset sharding."""

    def __init__(self, id, num_workers, dataset):
        self.id = id
        self.num_workers = num_workers
        self.dataset = dataset


def get_worker_info():
    return _WORKER_INFO


class _RingBase:
    """Shared mmap + native SPSC ring ops over it."""

    def _map(self, fd, size):
        self.mm = mmap.mmap(fd, size)
        self._buf = ctypes.c_char.from_buffer(self.mm)
        self.addr = ctypes.addressof(self._buf)

    def write(self, payload: bytes, timeout_ms=-1):
        r = native.LIB.ring_write(self.addr, payload, len(payload),
                                  timeout_ms)
        if r == -1:
            raise ValueError(
                f"batch of {len(payload)} bytes exceeds the shared ring "
                f"capacity; raise DataLoader(..., ring_bytes=)")
        if r == -2:
            raise TimeoutError("ring_write timed out (consumer stalled)")

    def close_producer(self):
        native.LIB.ring_close(self.addr)

    def next_len(self, timeout_ms):
        return native.LIB.ring_next_len(self.addr, timeout_ms)

    def read(self, n):
        out = ctypes.create_string_buffer(n)
        got = native.LIB.ring_read(self.addr, out, n)
        if got < 0:
            raise RuntimeError(f"ring_read error {got}")
        return out.raw[:got]

    def release(self):
        # drop the exported buffer before closing the mmap
        self._buf = None
        try:
            self.mm.close()
        except BufferError:  # pragma: no cover
            pass


class _Ring(_RingBase):
    """Parent-side ring: creates the backing file (in /dev/shm) + inits."""

    def __init__(self, size=_DEFAULT_RING_BYTES):
        fd, self.path = tempfile.mkstemp(prefix="pt_ring_", dir=_shm_dir())
        try:
            os.ftruncate(fd, size)
            self._map(fd, size)
        finally:
            os.close(fd)  # the mmap holds its own reference
        self.size = size
        if native.LIB.ring_init(self.addr, size) != 0:
            raise RuntimeError("ring_init failed")

    def release(self):
        super().release()
        try:
            os.unlink(self.path)
        except OSError:  # pragma: no cover
            pass


class _ChildRing(_RingBase):
    """Worker-side ring: attaches to the parent's backing file."""

    def __init__(self, path, size):
        fd = os.open(path, os.O_RDWR)
        try:
            self._map(fd, size)
        finally:
            os.close(fd)


def _to_numpy_tree(obj):
    """Convert a batch pytree to pure numpy/python for pickling.  Workers
    run on a cpu-forced jax platform, so device-backed Tensors created by
    the dataset/collate in the child convert safely; the parent re-wraps
    numpy into device Tensors after receipt."""
    from ..tensor import Tensor
    if isinstance(obj, Tensor):
        return np.asarray(obj._array)
    if isinstance(obj, (list, tuple)):
        return type(obj)(_to_numpy_tree(o) for o in obj)
    if isinstance(obj, dict):
        return {k: _to_numpy_tree(v) for k, v in obj.items()}
    return obj


def _worker_main(ring, worker_id, num_workers, dataset, batch_iter_fn,
                 collate_fn, init_fn, start_batch=0, chaos_directives=None,
                 chaos_seed=0):
    """Runs in the worker child: produce this worker's batch slice.

    `start_batch` supports crash recovery: a respawned worker re-drives
    its (deterministic) batch iterator from the top but only SHIPS
    batches the parent has not already consumed, so a respawn continues
    the epoch instead of replaying it.

    `chaos_directives` carries injected faults as positional batch
    ordinals (resolved by the parent's plan at spawn time — see
    resilience.chaos.take_loader_directives).

    Returns True on clean completion.  On error, ships an E-message and
    closes the ring; if even that fails, the ring is left OPEN and False
    is returned so the child exits nonzero and the parent's dead-worker
    check fires — a worker must never look 'cleanly finished' after an
    error (silently truncated epoch).
    """
    global _WORKER_INFO
    _WORKER_INFO = WorkerInfo(worker_id, num_workers, dataset)
    signal.signal(signal.SIGINT, signal.SIG_IGN)  # parent handles ^C
    cd = chaos_directives or {}
    corrupt_rng = None
    if cd.get("corrupt_p") is not None:
        import random as _random_mod
        # int mix, not a tuple seed (removed in python 3.11)
        corrupt_rng = _random_mod.Random(chaos_seed * 1000003 + worker_id)
    try:
        if init_fn is not None:
            init_fn(worker_id)
        for i, samples in enumerate(batch_iter_fn(worker_id, num_workers)):
            if i < start_batch:
                continue  # already consumed before our predecessor died
            ordinal = i + 1   # 1-based position in this worker's slice
            if cd.get("kill_at") == ordinal:
                os._exit(2)   # simulated SIGKILL/OOM: no E-message
            if cd.get("hang_at") == ordinal:
                while True:   # simulated wedge (parent's timeout fires)
                    time.sleep(3600)
            batch = _to_numpy_tree(collate_fn(samples))
            payload = pickle.dumps(batch, protocol=5)
            if cd.get("corrupt_at") == ordinal or (
                    corrupt_rng is not None and
                    corrupt_rng.random() < cd["corrupt_p"]):
                payload = b"\xde\xad" + payload[::-1]
            ring.write(b"B" + payload)
        ring.close_producer()
        return True
    except BaseException as e:
        for payload in (lambda: pickle.dumps((e, traceback.format_exc())),
                        lambda: pickle.dumps(
                            (None, f"{type(e).__name__} (unserializable "
                                   f"error payload)"))):
            try:
                ring.write(b"E" + payload(), timeout_ms=10_000)
                ring.close_producer()
                return False
            except Exception:
                continue
        return False  # ring left open → parent sees a dead worker


def serialize_spec(num_workers, dataset, batch_iter_fn, collate_fn,
                   worker_init_fn):
    """cloudpickle the work spec (by value: __main__/locally-defined
    datasets and closures cross to the worker like they did under fork).
    Raises whatever cloudpickle raises — callers that want a fallback
    probe this BEFORE constructing the pool."""
    import cloudpickle
    return cloudpickle.dumps(
        (num_workers, dataset, batch_iter_fn, collate_fn, worker_init_fn))


def _worker_entry(ring_path, ring_size, worker_id, spec_blob,
                  start_batch=0, chaos_directives=None, chaos_seed=0):
    """Forkserver child entrypoint (module-level: importable by name).

    The child NEVER touches the TPU — a chip belongs to one process, and
    it is the trainer's: force the child's jax platform to cpu before any
    user code runs, so a dataset that builds Tensors initializes a private
    CPU backend instead of failing (or hanging) on the parent's chip.
    """
    import jax
    try:
        jax.config.update("jax_platforms", "cpu")
    except Exception:  # pragma: no cover
        pass
    import cloudpickle
    code = 1
    try:
        num_workers, dataset, batch_iter_fn, collate_fn, init_fn = \
            cloudpickle.loads(spec_blob)
        ring = _ChildRing(ring_path, ring_size)
        # shrink the tmpfs-leak window on hard parent death: once both
        # sides are mapped the name is no longer needed (parent release
        # tolerates ENOENT)
        try:
            os.unlink(ring_path)
        except OSError:
            pass
        ok = _worker_main(ring, worker_id, num_workers, dataset,
                          batch_iter_fn, collate_fn, init_fn,
                          start_batch=start_batch,
                          chaos_directives=chaos_directives,
                          chaos_seed=chaos_seed)
        code = 0 if ok else 1
    finally:
        os._exit(code)  # skip atexit/GC teardown races


def _mp_context():
    import multiprocessing as mp
    ctx = mp.get_context("forkserver")
    # Amortize the package import (~4s) across all workers: the forkserver
    # server imports once, every worker forks from it instantly.  No-op
    # once the server is already running.
    try:
        ctx.set_forkserver_preload(["paddle_tpu.io.shm_loader"])
    except Exception:  # pragma: no cover
        pass
    return ctx


_PATCH_LOCK = threading.RLock()
_PATCH_DEPTH = 0
_PATCH_ORIG = None


@contextlib.contextmanager
def _no_main_reimport():
    """Strip the __main__-module fixup from mp's child preparation data.

    Workers never need the parent's __main__: the work spec crosses as a
    cloudpickle blob, which serializes __main__-defined datasets/functions
    BY VALUE.  Without this, spawn/forkserver children try to re-run the
    parent script (runpy), which (a) breaks for <stdin>/REPL parents and
    (b) re-executes unguarded training scripts — both unacceptable for a
    data-worker process.

    The patch is refcounted under a lock so concurrent/nested pools can't
    capture each other's wrapper and leave the stripped version installed
    permanently (which would break the user's own mp children).  Unrelated
    Processes started by other threads during the window do lose their
    __main__ re-import — the lock holds the window to the worker starts.
    """
    global _PATCH_DEPTH, _PATCH_ORIG
    from multiprocessing import spawn as mp_spawn
    with _PATCH_LOCK:
        if _PATCH_DEPTH == 0:
            _PATCH_ORIG = mp_spawn.get_preparation_data

            def stripped(name, _orig=_PATCH_ORIG):
                d = _orig(name)
                d.pop("init_main_from_name", None)
                d.pop("init_main_from_path", None)
                return d

            mp_spawn.get_preparation_data = stripped
        _PATCH_DEPTH += 1
        try:
            yield
        finally:
            _PATCH_DEPTH -= 1
            if _PATCH_DEPTH == 0:
                mp_spawn.get_preparation_data = _PATCH_ORIG
                _PATCH_ORIG = None


class ShmWorkerPool:
    """Start N forkserver workers, read their rings round-robin in batch
    order.

    Resilience: a worker that dies hard (SIGKILL/OOM/segfault) or wedges
    past `timeout_s` is respawned up to `max_respawns` times per slot
    with exponential backoff, resuming its batch slice after the batches
    the parent already consumed; a batch whose payload fails to
    deserialize is skipped and counted, not fatal.
    """

    _POLL_MS = 100  # bounded ring polls so worker death is noticed

    def __init__(self, num_workers, dataset, batch_iter_fn, collate_fn,
                 worker_init_fn=None, ring_bytes=_DEFAULT_RING_BYTES,
                 timeout_s=0, spec_blob=None, max_respawns=2,
                 respawn_backoff=None):
        if spec_blob is None:
            spec_blob = serialize_spec(num_workers, dataset, batch_iter_fn,
                                       collate_fn, worker_init_fn)
        self._spec_blob = spec_blob
        self._ctx = _mp_context()
        self._ring_bytes = ring_bytes
        self._timeout_ms = int(timeout_s * 1000) if timeout_s else -1
        self.max_respawns = int(os.environ.get(
            "PT_LOADER_MAX_RESPAWNS", str(max_respawns)))
        if respawn_backoff is None:
            from ..resilience.backoff import Backoff
            respawn_backoff = Backoff(base=0.2, max_delay=10.0)
        self._backoff = respawn_backoff
        self._rings = []
        self._procs = []
        self._consumed = [0] * num_workers   # batches read per slot
        self._respawns = [0] * num_workers
        try:
            for _ in range(num_workers):
                self._rings.append(_Ring(ring_bytes))
            with _no_main_reimport():
                for w in range(num_workers):
                    self._procs.append(self._spawn(w, self._rings[w]))
        except BaseException:
            self.shutdown()
            raise

    def _spawn(self, slot, ring, start_batch=0):
        # loader faults resolve against the PARENT's plan at spawn time:
        # its counters survive worker death, so a respawned worker does
        # not re-suffer the kill its predecessor already executed
        from ..resilience import chaos as _chaos
        plan = _chaos.active()
        directives = _chaos.take_loader_directives(slot) \
            if plan is not None else None
        p = self._ctx.Process(
            target=_worker_entry,
            args=(ring.path, ring.size, slot, self._spec_blob,
                  start_batch, directives,
                  plan.seed if plan is not None else 0),
            daemon=True)
        p.start()
        return p

    def _worker_dead(self, slot):
        """True if this slot's worker exited without closing the ring
        (SIGKILL/OOM/segfault) — data will never arrive."""
        return not self._procs[slot].is_alive()

    def _respawn(self, slot, reason):
        """Replace a dead/wedged worker: fresh ring + process resuming
        after the batches already consumed.  False when the respawn
        budget for this slot is exhausted."""
        if self._respawns[slot] >= self.max_respawns:
            return False
        attempt = self._respawns[slot]
        self._respawns[slot] += 1
        from .. import observability as _obs
        if _obs.enabled():
            _obs.metrics.registry().counter(
                "loader_worker_respawns_total").inc()
        warnings.warn(
            f"DataLoader worker {slot} {reason}; respawning "
            f"({self._respawns[slot]}/{self.max_respawns}, backoff "
            f"{self._backoff.delay(attempt):.2f}s)", RuntimeWarning)
        proc = self._procs[slot]
        if proc.is_alive():
            proc.terminate()
        proc.join()
        self._rings[slot].release()
        self._backoff.wait(attempt)
        ring = _Ring(self._ring_bytes)
        self._rings[slot] = ring
        with _no_main_reimport():
            self._procs[slot] = self._spawn(
                slot, ring, start_batch=self._consumed[slot])
        return True

    def __iter__(self):
        from .. import observability as _obs
        depth_gauge = wait_hist = skip_ctr = None
        if _obs.enabled():
            reg = _obs.metrics.registry()
            depth_gauge = reg.gauge("loader_queue_depth")
            wait_hist = reg.histogram("loader_batch_wait_seconds")
            skip_ctr = reg.counter("loader_batches_skipped_total")
        live = list(range(len(self._rings)))   # slot indices, not rings:
        w = 0                                  # a respawn swaps the ring
        waited_ms = 0
        wait_t0 = time.perf_counter()
        try:
            while live:
                slot = live[w % len(live)]
                ring = self._rings[slot]
                n = ring.next_len(self._POLL_MS)
                if n == -2:  # nothing yet: check liveness + user timeout
                    if self._worker_dead(slot) and \
                            ring.next_len(0) == -2:
                        if not self._respawn(slot, "died unexpectedly "
                                             "(killed / OOM?)"):
                            raise RuntimeError(
                                "DataLoader worker process died "
                                "unexpectedly (killed / OOM?); respawn "
                                f"budget ({self.max_respawns}) exhausted")
                        waited_ms = 0
                        continue
                    waited_ms += self._POLL_MS
                    if 0 <= self._timeout_ms < waited_ms:
                        if not self._respawn(slot, "timed out (wedged?)"):
                            raise TimeoutError(
                                "DataLoader worker timed out; respawn "
                                f"budget ({self.max_respawns}) exhausted")
                        waited_ms = 0
                    continue
                waited_ms = 0
                if n == -1:  # this worker is done
                    live.remove(slot)
                    continue
                payload = ring.read(n)
                if payload[:1] == b"E":
                    exc, tb = pickle.loads(payload[1:])
                    if exc is not None:  # re-raise with original type
                        raise exc from RuntimeError(
                            "DataLoader worker failed:\n" + tb)
                    raise RuntimeError("DataLoader worker failed:\n" + tb)
                try:
                    batch = pickle.loads(payload[1:])
                except Exception as e:
                    # poisoned/corrupt payload: losing one batch is
                    # recoverable, killing the run is not — skip, count,
                    # stay in round-robin order
                    self._consumed[slot] += 1
                    if skip_ctr is not None:
                        skip_ctr.inc()
                    warnings.warn(
                        f"DataLoader worker {slot}: corrupt batch payload "
                        f"({type(e).__name__}: {e}); batch skipped",
                        RuntimeWarning)
                    w += 1
                    wait_t0 = time.perf_counter()
                    continue
                self._consumed[slot] += 1
                if wait_hist is not None:
                    # time from requesting this batch until it was read,
                    # and how many workers have another batch ready (queue
                    # depth: 0 means the consumer is data-starved)
                    wait_hist.observe(time.perf_counter() - wait_t0)
                    depth_gauge.set(sum(1 for s in live
                                        if self._rings[s].next_len(0) >= 0))
                yield batch
                w += 1
                wait_t0 = time.perf_counter()
        finally:
            self.shutdown()

    def shutdown(self):
        for p in self._procs:
            if p.is_alive():
                p.terminate()
        for p in self._procs:
            p.join()
        self._procs = []
        for r in self._rings:
            r.release()
        self._rings = []
