"""Inference export — paddle.jit.save / paddle.jit.load parity.

Reference: python/paddle/jit/api.py (jit.save serializes the dy2static
Program + params to .pdmodel/.pdiparams; jit.load returns a
TranslatedLayer that replays the program).  TPU-native: the traced XLA
computation is serialized as portable StableHLO via `jax.export`, params
and buffers ride an .npz, and `load` returns a TranslatedLayer-like
callable that replays the compiled program — no Python model code needed
at load time, same as the reference's deployment story.

AOT deployment artifacts (`save_inference(..., aot=True)`): alongside
the portable StableHLO, the backend-compiled executable itself is
serialized (jax.experimental.serialize_executable), stamped with the
backend/mesh fingerprint it compiled for.  A compatible replica loads it
and serves its first request without ANY compilation — the serving
cold-start cost becomes a file read.  Compatibility is validated at
LOAD time (refuse-with-reason: platform, device kind/count, mesh, jax
version); an incompatible or damaged artifact falls back to the
portable StableHLO program with one warning — never a mid-step abort.
"""
from __future__ import annotations

import hashlib
import json
import os
import pickle
import warnings

import jax
import jax.numpy as jnp
import numpy as np
from jax import export as jexport

from ..dtypes import convert_dtype
from ..tensor import Tensor
from . import compile_cache as _cc
from . import functional_bridge as FB

_MODEL = "model.stablehlo"
_PARAMS = "params.npz"
_META = "inference_meta.json"
_AOT = "model.aotexec"


class AOTIncompatible(RuntimeError):
    """An AOT artifact cannot run on this host; `.reason` says why."""

    def __init__(self, reason):
        super().__init__(reason)
        self.reason = reason


class InputSpec:
    """paddle.static.InputSpec parity: symbolic input signature.

    `None` dims become export symbols (polymorphic batch, etc.).
    """

    def __init__(self, shape, dtype="float32", name=None):
        self.shape = tuple(shape)
        self.dtype = convert_dtype(dtype)
        self.name = name

    @classmethod
    def from_tensor(cls, t, name=None):
        return cls(tuple(t.shape), t.dtype, name)

    def __repr__(self):
        return f"InputSpec(shape={self.shape}, dtype={self.dtype})"


def _to_spec(s):
    if isinstance(s, InputSpec):
        return s
    if isinstance(s, Tensor):
        return InputSpec(tuple(s.shape), s._array.dtype)
    if hasattr(s, "shape") and hasattr(s, "dtype"):
        return InputSpec(tuple(s.shape), s.dtype)
    raise TypeError(f"bad input_spec entry: {s!r}")


def _shape_structs(specs):
    """ShapeDtypeStructs for the export trace; None dims → shared-scope
    export symbols so one program serves any batch size."""
    has_dynamic = any(d is None for s in specs for d in s.shape)
    scope = jexport.SymbolicScope() if has_dynamic else None
    out = []
    sym_i = 0
    for s in specs:
        parts = []
        for d in s.shape:
            if d is None:
                parts.append(f"_d{sym_i}")
                sym_i += 1
            else:
                parts.append(str(d))
        if any(p.startswith("_d") for p in parts):
            shape = jexport.symbolic_shape(", ".join(parts), scope=scope)
        else:
            shape = tuple(int(d) for d in s.shape)
        out.append(jax.ShapeDtypeStruct(shape, s.dtype))
    return out


def save_inference(layer, path, input_spec, aot=False):
    """Trace `layer.forward` over `input_spec` (eval mode) and serialize the
    StableHLO program + params to directory `path`.

    `aot=True` additionally compiles the program for THIS backend and
    serializes the executable as a mesh/version-stamped deployment
    artifact: a compatible replica's `load` skips compilation entirely.
    AOT needs concrete shapes (no None dims — an executable is shape-
    specialized); the portable StableHLO keeps serving every other host.
    """
    from ..nn.layer import Layer
    if not isinstance(layer, Layer):  # StaticFunction wrapper
        layer = layer.layer
    specs = [_to_spec(s) for s in input_spec]
    if aot and any(d is None for s in specs for d in s.shape):
        raise ValueError(
            "aot=True requires concrete input shapes: a compiled "
            "executable is specialized per shape (use explicit batch "
            "sizes, or shape buckets — one artifact per bucket)")
    path = os.path.abspath(path)
    os.makedirs(path, exist_ok=True)

    pn, pa, bn, ba = FB.split_state(layer)
    # eval() recurses into sublayers, so capture every layer's mode
    prev_modes = [(l, l.training) for l in [layer] + list(layer.sublayers())]
    layer.eval()
    try:
        def pure(p_arrays, b_arrays, in_arrays):
            out, _ = FB.call_functional(
                layer, p_arrays, b_arrays, in_arrays,
                rng_key=jax.random.PRNGKey(0))
            return out

        in_structs = _shape_structs(specs)
        p_structs = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in pa]
        b_structs = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in ba]
        jitted = jax.jit(pure)
        exported = jexport.export(jitted)(
            p_structs, b_structs, in_structs)
        aot_meta = None
        if aot:
            aot_meta = _write_aot(jitted, path,
                                  (p_structs, b_structs, in_structs))
    finally:
        for l, mode in prev_modes:
            l.training = mode

    with open(os.path.join(path, _MODEL), "wb") as f:
        f.write(exported.serialize())
    np.savez(os.path.join(path, _PARAMS),
             **{f"p{i}": np.asarray(a) for i, a in enumerate(pa)},
             **{f"b{i}": np.asarray(a) for i, a in enumerate(ba)})
    meta = {"n_params": len(pa), "n_buffers": len(ba),
            "param_names": pn, "buffer_names": bn,
            "input_spec": [{"shape": [d if d is None else int(d)
                                      for d in s.shape],
                            "dtype": str(np.dtype(s.dtype))}
                           for s in specs]}
    if aot_meta is not None:
        meta["aot"] = aot_meta
    with open(os.path.join(path, _META), "w") as f:
        json.dump(meta, f)


def _env_stamp():
    jx, jl, plat, kind, n = _cc.env_fingerprint()
    return {"jax": jx, "jaxlib": jl, "platform": plat,
            "device_kind": kind, "n_devices": n,
            "mesh": _cc.mesh_fingerprint()}


def _write_aot(jitted, path, example_structs):
    compiled = jitted.lower(*example_structs).compile()
    payload = pickle.dumps(_cc.serialize_compiled(compiled))
    with open(os.path.join(path, _AOT), "wb") as f:
        f.write(payload)
    stamp = _env_stamp()
    stamp["sha256"] = hashlib.sha256(payload).hexdigest()
    return stamp


def _aot_compatible(stamp):
    """(ok, reason) — load-time validation of an AOT stamp against this
    host.  Every refusal names exactly what diverged."""
    cur = _env_stamp()
    for k, what in (("platform", "backend platform"),
                    ("device_kind", "device kind"),
                    ("n_devices", "device count"),
                    ("mesh", "mesh topology"),
                    ("jax", "jax version"),
                    ("jaxlib", "jaxlib version")):
        if stamp.get(k) != cur[k]:
            return False, (f"{what} mismatch: artifact compiled for "
                           f"{stamp.get(k)!r}, this host is {cur[k]!r}")
    return True, ""


class TranslatedLayer:
    """Replays a serialized inference program (reference: TranslatedLayer).

    With a loaded AOT executable (`aot_exec`) calls dispatch straight to
    the deserialized executable — zero compilation; otherwise the
    portable StableHLO path recompiles once per process.
    """

    def __init__(self, exported, params, buffers, meta, aot_exec=None):
        self._exported = exported
        self._params = params
        self._buffers = buffers
        self._meta = meta
        self._aot = aot_exec

    @property
    def is_aot(self):
        return self._aot is not None

    def __call__(self, *inputs):
        arrays = [i._array if isinstance(i, Tensor) else jnp.asarray(i)
                  for i in inputs]
        if self._aot is not None:
            try:
                out = self._aot(self._params, self._buffers, arrays)
                return FB._rewrap(tuple(out) if isinstance(out, list)
                                  else out)
            except TypeError as e:
                # arg signature drifted from what the artifact compiled
                # for (e.g. a different batch size): degrade to the
                # portable program, never abort the serving step
                warnings.warn(
                    f"AOT executable rejected this call signature ({e}); "
                    f"falling back to the portable StableHLO program",
                    UserWarning, stacklevel=2)
                self._aot = None
        out = self._exported.call(self._params, self._buffers, arrays)
        return FB._rewrap(out)

    forward = __call__

    def eval(self):
        return self

    def train(self):
        raise RuntimeError("TranslatedLayer is inference-only")


def _load_aot(path, meta):
    """The deserialized AOT executable, or (None, reason)."""
    stamp = meta.get("aot")
    aot_path = os.path.join(path, _AOT)
    if stamp is None or not os.path.exists(aot_path):
        return None, "no AOT artifact in this export"
    ok, reason = _aot_compatible(stamp)
    if not ok:
        return None, reason
    try:
        with open(aot_path, "rb") as f:
            payload = f.read()
        if hashlib.sha256(payload).hexdigest() != stamp.get("sha256"):
            return None, "artifact checksum mismatch (damaged file)"
        return _cc.load_compiled(pickle.loads(payload)), ""
    except Exception as e:  # damaged/foreign payload: fall back
        return None, f"artifact failed to load: {e}"


def load_inference(path, prefer_aot=True, strict_aot=False):
    """Load an inference export.  When the export carries an AOT
    executable compatible with this host it is used (first call needs no
    compilation); an incompatible one is refused WITH the reason and the
    portable StableHLO program serves instead.  `strict_aot=True` turns
    that refusal into AOTIncompatible — for deployments where a silent
    recompile (minutes of cold start) is worse than a hard error."""
    path = os.path.abspath(path)
    with open(os.path.join(path, _MODEL), "rb") as f:
        exported = jexport.deserialize(f.read())
    with open(os.path.join(path, _META)) as f:
        meta = json.load(f)
    z = np.load(os.path.join(path, _PARAMS))
    params = [jnp.asarray(z[f"p{i}"]) for i in range(meta["n_params"])]
    buffers = [jnp.asarray(z[f"b{i}"]) for i in range(meta["n_buffers"])]
    aot_exec = None
    if prefer_aot:
        aot_exec, reason = _load_aot(path, meta)
        if aot_exec is None and meta.get("aot") is not None:
            if strict_aot:
                raise AOTIncompatible(reason)
            warnings.warn(
                f"AOT artifact refused: {reason}; falling back to the "
                f"portable StableHLO program (will recompile once)",
                UserWarning, stacklevel=2)
            from ..observability import metrics as _metrics
            _metrics.registry().counter(
                "aot_artifact_refused_total").inc()
    return TranslatedLayer(exported, params, buffers, meta,
                           aot_exec=aot_exec)


def is_inference_dir(path):
    return os.path.isdir(path) and \
        os.path.exists(os.path.join(path, _MODEL))
