"""Place / device abstraction.

Reference surface: paddle.device.set_device / CUDAPlace / CPUPlace / XPUPlace
(python/paddle/device/__init__.py).  TPU-native: a Place names a jax device;
``tpu`` is the first-class accelerator.  There are no streams to manage —
XLA's async dispatch replaces the reference's stream/event machinery.
"""
from __future__ import annotations

import glob
import os

import jax


class Place:
    device_type = "unknown"

    def __init__(self, device_id: int = 0):
        self.device_id = int(device_id)

    def __repr__(self):
        return f"Place({self.device_type}:{self.device_id})"

    def __eq__(self, other):
        return (
            isinstance(other, Place)
            and self.device_type == other.device_type
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def jax_device(self):
        # an explicit place never lands somewhere else: no such backend
        # raises (jax: "Backend 'tpu' failed to initialize ...")
        devs = jax.devices(self.device_type)
        return devs[min(self.device_id, len(devs) - 1)]


class TPUPlace(Place):
    device_type = "tpu"


class CPUPlace(Place):
    device_type = "cpu"


_current_place = [None]


def _default_place() -> Place:
    return TPUPlace(0) if is_compiled_with_tpu() else CPUPlace(0)


def set_device(device: str):
    """set_device("tpu") / set_device("tpu:0") / set_device("cpu")."""
    name, _, idx = device.partition(":")
    idx = int(idx) if idx else 0
    if name in ("tpu", "gpu", "xpu", "npu"):  # accelerator aliases all map to tpu
        _current_place[0] = TPUPlace(idx)
    elif name == "cpu":
        _current_place[0] = CPUPlace(idx)
    else:
        raise ValueError(f"unknown device {device!r}")
    return _current_place[0]


def get_device() -> str:
    p = current_place()
    return f"{p.device_type}:{p.device_id}"


def current_place() -> Place:
    if _current_place[0] is None:
        _current_place[0] = _default_place()
    return _current_place[0]


def is_compiled_with_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


def is_compiled_with_cuda() -> bool:
    return False  # TPU-native build (reference parity shim)


def is_compiled_with_xpu() -> bool:
    return False


def device_count() -> int:
    return len(jax.devices())


def tpu_chips_visible() -> int:
    """TPU chips of this host, counted WITHOUT initialising a backend —
    for a parent process that must leave the chip to its children (a chip
    belongs to one process at a time).  0 where the environment holds jax
    to another platform."""
    plats = os.environ.get("JAX_PLATFORMS", "")
    if plats and "tpu" not in plats.split(","):
        return 0
    return (len(glob.glob("/dev/accel[0-9]*"))
            or len(glob.glob("/dev/vfio/[0-9]*")))


# ------------------------------------------------------- cuda-compat shims
class _CudaNamespace:
    """paddle.device.cuda compatibility (reference: python/paddle/device/
    cuda/__init__.py).  Ported user code calls these around training
    loops; on the XLA runtime memory is pool-managed and dispatch is
    async by design, so the knobs are truthful no-ops / TPU remaps."""

    @staticmethod
    def device_count():
        import jax
        return len([d for d in jax.devices() if d.platform != "cpu"])

    @staticmethod
    def empty_cache():
        pass  # XLA BFC allocator owns the pool

    @staticmethod
    def synchronize(device=None):
        import jax
        import jax.numpy as jnp
        jax.block_until_ready(jnp.zeros(()))

    @staticmethod
    def max_memory_allocated(device=None):
        import jax
        try:
            stats = jax.devices()[0].memory_stats() or {}
            return int(stats.get("peak_bytes_in_use", 0))
        except Exception:
            return 0

    @staticmethod
    def memory_allocated(device=None):
        import jax
        try:
            stats = jax.devices()[0].memory_stats() or {}
            return int(stats.get("bytes_in_use", 0))
        except Exception:
            return 0

    @staticmethod
    def get_device_name(device=None):
        import jax
        return jax.devices()[0].device_kind

    class Stream:
        """Streams do not exist on the XLA runtime (dispatch is async,
        ordering is data-flow); kept for API-compatible construction."""

        def __init__(self, *a, **kw):
            pass

    class Event:
        def __init__(self, *a, **kw):
            pass

        def record(self, *a, **kw):
            pass

        def synchronize(self):
            _CudaNamespace.synchronize()


cuda = _CudaNamespace()
