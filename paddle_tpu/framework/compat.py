"""The one place this repo touches jax surfaces that have moved before.

`shard_map`, `axis_size` and `cost_analysis()` changed shape across jax
releases; call sites go through here so that the next move is one edit.
The code is for the jax that is installed (0.9): no branch for another.
"""
from __future__ import annotations

import jax
from jax import lax


def axis_index(axis_name):
    return lax.axis_index(axis_name)


def axis_size(axis_name):
    """Inside a mapped body this resolves to a concrete Python int."""
    return lax.axis_size(axis_name)


def shard_map(f, mesh, in_specs, out_specs, check_vma=None,
              axis_names=None):
    """`jax.shard_map`.  `axis_names` — mesh axes to run in MANUAL mode
    (partial-manual: GSPMD keeps the rest); omitted means all axes.
    `check_vma` — the value-and-mesh-agreement check."""
    kw = {"mesh": mesh, "in_specs": in_specs, "out_specs": out_specs}
    if check_vma is not None:
        kw["check_vma"] = check_vma
    if axis_names is not None:
        kw["axis_names"] = set(axis_names)
    return jax.shard_map(f, **kw)


def normalize_cost_analysis(cost):
    """`Compiled.cost_analysis()` as a dict; a backend with no cost model
    returns None.  Callers (paddle.flops, profiler.program_stats, the
    sparse-conv FLOP assertions) read keys like ``"flops"``."""
    return dict(cost) if cost else {}
