"""Per-bucket AOT serving artifacts — zero-compile warm replica start.

The remaining PR 7 follow-up (ROADMAP item 4): a serving replica's whole
program inventory — one prefill executable per shape bucket plus THE
decode program — is AOT-compiled and serialized the way
`jit.save(aot=True)` stamps inference artifacts, so a warm replica
deserializes executables instead of tracing+compiling anything.

Layout under `path/`:

    serving_manifest.json   program inventory + env/mesh stamp + sha256s
    programs/<name>.aotexec pickled serialized executables

Compatibility is validated at LOAD time with the same refuse-with-reason
stamp checks as `jit.load_inference` (platform, device kind/count, mesh,
jax/jaxlib versions) and against the layout of what the engine's
programs take and return; a refused or damaged artifact is skipped with the
reason — the engine's live-jit path serves instead, never an abort.

Trade-off baked into the format: serialized executables are ALIAS-FREE
(deserializing alias-baked donation is the PR 7 segfault class), so a
warm-started replica's steps copy the pool instead of donating it on
backends where the live jit would donate.  The artifacts buy INSTANT
first-token serving; once warm, `engine.retire_aot()` drops the bridge
executables so the next call compiles the donating live program at a
moment the operator chooses — never as a surprise cold-start stall.
"""
from __future__ import annotations

import hashlib
import json
import os
import pickle
import warnings

from ..jit import compile_cache as _cc
from ..jit.save_load import AOTIncompatible, _aot_compatible, _env_stamp
from ..observability import metrics as _metrics

_MANIFEST = "serving_manifest.json"
_PROGRAMS = "programs"
# What the programs take and return, as the engine calls and unpacks
# them; a manifest without the key is layout 1.  2: the decode program
# returns (logits, ids, finite, planes, *load) where it returned
# (logits, planes, *load): the same arguments, so nothing but this
# number would refuse the older one.  3: the decode program takes two
# more arguments, `prev_ids` and `src` (a chained row's token is the
# pick of the program before, read on the device); an older executable
# would refuse every call with a TypeError and a warning a step, so it
# is refused once, here, with the reason
_LAYOUT = 3


def _key_name(key):
    return "_".join(str(p) for p in key)


def _name_key(name):
    parts = name.split("_")
    return tuple(int(p) if p.isdigit() else p for p in parts)


def export_serving_artifacts(engine, path, prompt_lens=()):
    """AOT-compile and serialize the engine's program inventory.

    `prompt_lens` widens the prefill bucket coverage to the prompt
    lengths this replica expects (chunks it would cut); the decode
    program and the base chunk bucket are always included.  Returns the
    manifest dict."""
    path = os.path.abspath(path)
    os.makedirs(os.path.join(path, _PROGRAMS), exist_ok=True)
    manifest = {"stamp": _env_stamp(), "layout": _LAYOUT, "programs": {}}
    for key in engine.program_keys(prompt_lens=prompt_lens):
        # always an alias-free twin from program_structs' builder — the
        # engine's LIVE program may donate the pool buffers, and a
        # serialized alias-baked executable segfaults on deserialize
        # (the PR-7 hazard); the twin is never installed as the live
        # program
        builder, structs = engine.program_structs(key)
        compiled = builder().lower(*structs).compile()
        payload = pickle.dumps(_cc.serialize_compiled(compiled))
        name = _key_name(key)
        fn = os.path.join(_PROGRAMS, f"{name}.aotexec")
        with open(os.path.join(path, fn), "wb") as f:
            f.write(payload)
        manifest["programs"][name] = {
            "file": fn, "sha256": hashlib.sha256(payload).hexdigest()}
        _metrics.registry().counter("serving_aot_exported_total").inc()
    with open(os.path.join(path, _MANIFEST), "w") as f:
        json.dump(manifest, f, indent=1)
    return manifest


def load_serving_artifacts(engine, path, strict=False):
    """Install AOT executables from `path` into the engine.  Returns the
    list of loaded program keys.  Incompatible/damaged artifacts are
    refused WITH the reason (warning + counter); `strict=True` raises
    AOTIncompatible instead — for replicas where a silent cold compile
    is worse than failing the deploy."""
    path = os.path.abspath(path)
    try:
        with open(os.path.join(path, _MANIFEST)) as f:
            manifest = json.load(f)
    except (OSError, ValueError) as e:
        if strict:
            raise AOTIncompatible(f"unreadable serving manifest: {e}")
        warnings.warn(f"no serving AOT manifest at {path} ({e}); "
                      f"cold start will compile", UserWarning, stacklevel=2)
        return []
    ok, reason = _aot_compatible(manifest.get("stamp", {}))
    if ok and manifest.get("layout", 1) != _LAYOUT:
        ok, reason = False, (
            f"program layout mismatch: artifact exported for layout "
            f"{manifest.get('layout', 1)!r}, this engine's programs "
            f"are layout {_LAYOUT}")
    if not ok:
        if strict:
            raise AOTIncompatible(reason)
        warnings.warn(
            f"serving AOT artifacts refused: {reason}; live jit serves "
            f"instead (cold compile)", UserWarning, stacklevel=2)
        _metrics.registry().counter("serving_aot_refused_total").inc()
        return []
    loaded = []
    for name, entry in manifest.get("programs", {}).items():
        try:
            with open(os.path.join(path, entry["file"]), "rb") as f:
                payload = f.read()
            if hashlib.sha256(payload).hexdigest() != entry.get("sha256"):
                raise ValueError("artifact checksum mismatch")
            exec_ = _cc.load_compiled(pickle.loads(payload))
        except Exception as e:
            if strict:
                raise AOTIncompatible(f"program {name}: {e}")
            warnings.warn(
                f"serving AOT program {name} refused ({e}); it will "
                f"compile live", UserWarning, stacklevel=2)
            _metrics.registry().counter("serving_aot_refused_total").inc()
            continue
        key = _name_key(name)
        engine._aot_execs[key] = exec_
        loaded.append(key)
        _metrics.registry().counter("serving_aot_loaded_total").inc()
    return loaded
