"""Fully-jitted autoregressive decoding (reference analog: the reference's
dy2static + fused inference graph for generation — ERNIE/GPT inference via
CINN; here the ENTIRE decode loop, prefill + lax.while_loop over tokens,
is ONE XLA program, so a 100-token generation costs one dispatch instead
of 100 host round-trips).

Models opt in by supporting the preallocated KV cache: a cache dict
{"k": [b, max_len, H, D], "v": ..., "pos": int32 scalar} whose sequence
slot is written at the traced offset (ops "dyn_update_seq") and whose
attention is masked to `col <= pos + row` — static shapes throughout,
which is what lets XLA compile the loop.
"""
from __future__ import annotations

import collections

import jax
import jax.numpy as jnp
from jax import lax

from ..autograd import engine
from ..jit import functional_bridge as FB
from ..tensor import Tensor


def _lru_compiled(store, key, build, cap=8):
    """Pop-reinsert LRU over a dict of compiled programs."""
    fn = store.pop(key, None)
    if fn is None:
        fn = build()
    store[key] = fn
    while len(store) > cap:
        store.pop(next(iter(store)))
    return fn


def _update_prealloc_cache(cache, k, v, s, window=None):
    """Write k/v at cache['pos'] and return full buffers + bool attn mask.
    pos may be scalar (shared offset) or [b] (per-row offsets).  With
    ``window`` (sliding-window attention) a row at absolute position r
    attends cache slots in (r-window, r] instead of [0, r]."""
    from .. import tensor_api as T
    from ..ops import call as ops_call
    pos = cache["pos"]
    cache["k"] = ops_call("dyn_update_seq", cache["k"], k, pos)
    cache["v"] = ops_call("dyn_update_seq", cache["v"], v, pos)
    K, V = cache["k"], cache["v"]
    L = K.shape[1]
    cols = T.arange(L, dtype="int32").unsqueeze(0)          # [1, L]
    if pos.ndim == 0:
        rows = (pos.astype("int32")
                + T.arange(s, dtype="int32")).unsqueeze(1)   # [s, 1]
        mask = cols <= rows
        if window:
            mask = mask & (cols > rows - window)
        mask = mask.reshape([1, 1, s, L])
    else:
        rows = (pos.astype("int32").unsqueeze(1)
                + T.arange(s, dtype="int32").unsqueeze(0))   # [b, s]
        mask = rows.unsqueeze(2) >= cols.unsqueeze(0)        # [b, s, L]
        if window:
            mask = mask & (rows.unsqueeze(2) - window
                           < cols.unsqueeze(0))
        mask = mask.unsqueeze(1)                             # [b, 1, s, L]
    return K, V, mask


def kv_cache_planes(cfg):
    """What a K/V model caches per token per layer, for the serving pool
    (`BlockPool.for_model`): `k` and `v` of [kv heads, head dim]; GQA
    models keep unrepeated kv heads."""
    hd = cfg.hidden_size // cfg.num_heads
    hkv = getattr(cfg, "num_kv_heads", None) or cfg.num_heads
    return [{"k": (hkv, hd), "v": (hkv, hd)}] * cfg.num_layers


class LayerPlanes(dict):
    """One layer's planes ({name: trailing shape per token | StatePlane})
    with the KIND of lifetime its blocks have: `window` None, the whole
    context stays (what a plain dict means too); a number, the layer
    reads the last `window` positions alone, and the serving pool gives
    the layers of that kind blocks, a free list and a table a request of
    their own and takes a block back once it lies wholly behind the
    band.  `kind` names the group in counters and refusals."""

    def __init__(self, planes, kind=None, window=None):
        super().__init__(planes)
        self.window = None if window is None else int(window)
        self.kind = kind or ("full" if window is None else "window")


class StatePlane(collections.namedtuple("StatePlane", "shape dtype")):
    """A plane a layer caches per REQUEST, not per token (a recurrent
    state, a convolution's tail): its shape, and its dtype where that is
    not the pool's (None = the pool's).  `cache_planes()` names it where
    a per-token plane gives its trailing shape."""
    __slots__ = ()


def _update_paged_cache(cache, k, v):
    """Serving path: write k/v [b, s, H, D] into the block-paged pool at
    each row's context offset and return (k_pool, v_pool) for the paged
    attention op.  The cache dict carries the pool view the engine
    assembled for this step: {"k"/"v": [N, bs, Hkv, D] pool Tensors,
    "table": [b, M] block ids, "pos": [b] context offsets, "limit": [b]
    write ceilings (pos + real chunk length; 0 for dead decode slots)}.
    Like `_update_prealloc_cache` this is write-THEN-attend: the current
    chunk's keys are visible to its own queries."""
    from ..ops import call as ops_call
    bs = cache["k"].shape[1]
    cache["k"] = ops_call("paged_write", cache["k"], k, cache["table"],
                          cache["pos"], cache["limit"], block_size=bs)
    cache["v"] = ops_call("paged_write", cache["v"], v, cache["table"],
                          cache["pos"], cache["limit"], block_size=bs)
    return cache["k"], cache["v"]


def _sample(logits, key, do_sample, temperature, top_k, top_p):
    from .generation import filter_logits
    logits = logits.astype(jnp.float32)
    if not do_sample:
        return jnp.argmax(logits, axis=-1)
    return jax.random.categorical(
        key, filter_logits(logits, temperature, top_k, top_p), axis=-1)


def _truncate_at_eos(out, prompt_len, eos_token_id):
    """Match the eager loop's early-exit shape: truncate after the LAST
    row finishes (positions past a row's eos are eos-padded)."""
    import numpy as np
    host = np.asarray(out)
    gen = host[:, prompt_len:]
    hit = gen == eos_token_id
    first = np.where(hit.any(1), hit.argmax(1), gen.shape[1] - 1)
    return host[:, :prompt_len + int(first.max()) + 1]


import contextlib


@contextlib.contextmanager
def _eval_mode(*models):
    """Temporarily switch models to eval; restore train flags on exit."""
    states = [m.training for m in models]
    for m in models:
        m.eval()
    try:
        yield
    finally:
        for m, was in zip(models, states):
            if was:
                m.train()


def _decode_state(model, batch, max_length):
    """split_state + preallocated-cache arrays for a jitted decode."""
    pn, p_arrays, bn, b_arrays = FB.split_state(model)
    proto = model.new_caches(batch, dtype=p_arrays[0].dtype,
                             max_length=max_length)
    # whatever the model caches per layer (K and V, or one latent row),
    # by name; the offset travels beside it
    caches = [{k: t._array for k, t in c.items() if k != "pos"}
              for c in proto]
    return pn, p_arrays, bn, b_arrays, caches


def _model_step(model, pn, bn, p_arrays, b_arrays, ids, cache_arrays, pos):
    """One functional forward over the preallocated caches."""
    caches = [dict({k: Tensor._from_array(a) for k, a in c.items()},
                   pos=Tensor._from_array(pos))
              for c in cache_arrays]
    with FB._swapped(model, pn, p_arrays, bn, b_arrays):
        with engine.no_grad():
            logits = model(Tensor._from_array(ids), caches=caches)
    new_cache_arrays = [{k: new[k]._array for k in old}
                        for new, old in zip(caches, cache_arrays)]
    return logits._array, new_cache_arrays


def jit_generate(model, input_ids, max_new_tokens=20, do_sample=False,
                 temperature=1.0, top_k=None, top_p=None, eos_token_id=None,
                 seed_key=None):
    """Compile prefill + decode into one XLA program; returns
    [b, prompt + max_new_tokens] ids (positions after eos hold eos)."""
    from ..framework import random as _random
    b, prompt_len = input_ids.shape
    total = prompt_len + max_new_tokens
    with _eval_mode(model):
        pn, p_arrays, bn, b_arrays, cache_arrays = _decode_state(
            model, b, total)
        key = seed_key if seed_key is not None else _random.next_key()

        cache_key = (prompt_len, max_new_tokens, bool(do_sample),
                     float(temperature), top_k, top_p, eos_token_id, b)
        cache = model.__dict__.setdefault("_jit_decode_cache", {})

        def _build():
            def pure(p_arrays, b_arrays, ids, cache_arrays, key):
                ids = ids.astype(jnp.int32)
                logits, cache_arrays = _model_step(
                    model, pn, bn, p_arrays, b_arrays, ids, cache_arrays,
                    jnp.asarray(0, jnp.int32))
                key, sub = jax.random.split(key)
                nxt = _sample(logits[:, -1, :], sub, do_sample, temperature,
                              top_k, top_p).astype(jnp.int32)
                # eos-fill so rows that finish early read as eos-padded even
                # when the whole loop exits before writing the tail
                fill = eos_token_id if eos_token_id is not None else 0
                buf = jnp.full((b, total), fill, jnp.int32)
                buf = lax.dynamic_update_slice(buf, ids, (0, 0))
                buf = buf.at[:, prompt_len].set(nxt)
                finished = jnp.zeros((b,), bool) if eos_token_id is not None \
                    else None
                if finished is not None:
                    finished = finished | (nxt == eos_token_id)

                def cond(state):
                    i, _, _, _, fin = state
                    alive = jnp.asarray(True) if fin is None else ~fin.all()
                    return (i < total) & alive

                def body(state):
                    i, buf, cache_arrays, key, fin = state
                    cur = lax.dynamic_slice(buf, (0, i - 1), (b, 1))
                    logits, cache_arrays = _model_step(
                        model, pn, bn, p_arrays, b_arrays, cur,
                        cache_arrays, i - 1)
                    key, sub = jax.random.split(key)
                    nxt = _sample(logits[:, -1, :], sub, do_sample,
                                  temperature, top_k, top_p).astype(jnp.int32)
                    if fin is not None:
                        nxt = jnp.where(fin, eos_token_id, nxt)
                        fin = fin | (nxt == eos_token_id)
                    buf = lax.dynamic_update_slice(buf, nxt[:, None], (0, i))
                    return (i + 1, buf, cache_arrays, key, fin)

                state = (jnp.asarray(prompt_len + 1, jnp.int32), buf,
                         cache_arrays, key, finished)
                _, buf, _, _, _ = lax.while_loop(cond, body, state)
                return buf

            return jax.jit(pure)

        fn = _lru_compiled(cache, cache_key, _build)
        out = fn(p_arrays, b_arrays, input_ids._array, cache_arrays, key)
        if eos_token_id is not None:
            out = _truncate_at_eos(out, prompt_len, eos_token_id)
        return Tensor._from_array(jnp.asarray(out))


def jit_beam_search(model, input_ids, beam_size=4, max_new_tokens=20,
                    length_penalty=1.0, eos_token_id=None):
    """Beam-search decode as ONE jitted XLA program (prefill + while_loop
    over tokens), token-compatible with the eager
    ``generation.beam_search``: beams ride the batch axis ([b*beam]),
    every step is one batched forward over the preallocated KV caches,
    and each beam reorder gathers the cache rows in-program (a device
    gather XLA keeps inside the loop — no host round-trips).

    Returns [b, prompt + max_new_tokens]; with ``eos_token_id`` the
    positions after a winning beam finishes hold eos (the frozen-beam
    continuation), where the eager loop would have stopped early.
    """
    beam = int(beam_size)
    b, prompt_len = input_ids.shape
    bb = b * beam
    total = prompt_len + max_new_tokens
    with _eval_mode(model):
        pn, p_arrays, bn, b_arrays, cache_arrays = _decode_state(
            model, bb, total)

        ckey = ("beam", prompt_len, max_new_tokens, beam,
                float(length_penalty), eos_token_id, b)
        jcache = model.__dict__.setdefault("_jit_decode_cache", {})

        def _build():
            def gather_caches(caches, g):
                return [{k: a[g] for k, a in c.items()} for c in caches]

            def pure(p_arrays, b_arrays, ids, caches):
                ids = jnp.repeat(ids.astype(jnp.int32), beam, axis=0)
                logits, caches = _model_step(
                    model, pn, bn, p_arrays, b_arrays, ids, caches,
                    jnp.asarray(0, jnp.int32))
                logp = jax.nn.log_softmax(
                    logits[:, -1, :].astype(jnp.float32), axis=-1)
                V = logp.shape[-1]
                # step 0: all beams identical — only beam 0 competes
                init = jnp.tile(jnp.asarray([0.0] + [-1e9] * (beam - 1)),
                                b)[:, None]
                scores = (logp + init).reshape(b, beam * V)
                beam_scores, top = jax.lax.top_k(scores, beam)
                src_beam = top // V
                tok = (top % V).astype(jnp.int32)
                g = (jnp.arange(b)[:, None] * beam + src_beam).reshape(-1)
                fill = eos_token_id if eos_token_id is not None else 0
                buf = jnp.full((bb, total), fill, jnp.int32)
                buf = lax.dynamic_update_slice(buf, ids, (0, 0))
                buf = buf[g].at[:, prompt_len].set(tok.reshape(-1))
                caches = gather_caches(caches, g)
                beam_scores = beam_scores.reshape(-1)
                finished = jnp.zeros((bb,), bool)
                if eos_token_id is not None:
                    finished = buf[:, prompt_len] == eos_token_id
                gen_lens = jnp.ones((bb,), jnp.float32)

                def cond(state):
                    i, _, _, fin, _, _ = state
                    alive = jnp.asarray(True) if eos_token_id is None \
                        else ~fin.all()
                    return (i < total) & alive

                def body(state):
                    i, buf, beam_scores, finished, gen_lens, caches = state
                    cur = lax.dynamic_slice(buf, (0, i - 1), (bb, 1))
                    logits, caches = _model_step(
                        model, pn, bn, p_arrays, b_arrays, cur, caches,
                        i - 1)
                    logp = jax.nn.log_softmax(
                        logits[:, -1, :].astype(jnp.float32), axis=-1)
                    if eos_token_id is not None:
                        # finished beams only extend with eos, score kept
                        frozen = jnp.full((V,), -jnp.inf).at[
                            eos_token_id].set(0.0)
                        logp = jnp.where(finished[:, None],
                                         frozen[None, :], logp)
                    scores = (beam_scores[:, None] + logp).reshape(
                        b, beam * V)
                    bs, top = jax.lax.top_k(scores, beam)
                    src_beam = top // V
                    tok = (top % V).astype(jnp.int32)
                    g = (jnp.arange(b)[:, None] * beam
                         + src_beam).reshape(-1)
                    buf = lax.dynamic_update_slice(
                        buf[g], tok.reshape(-1, 1), (0, i))
                    caches = gather_caches(caches, g)
                    gen_lens = gen_lens[g] + (~finished[g]).astype(
                        jnp.float32)
                    if eos_token_id is not None:
                        finished = finished[g] | (tok.reshape(-1)
                                                  == eos_token_id)
                    else:
                        finished = finished[g]
                    return (i + 1, buf, bs.reshape(-1), finished,
                            gen_lens, caches)

                state = (jnp.asarray(prompt_len + 1, jnp.int32), buf,
                         beam_scores, finished, gen_lens, caches)
                _, buf, beam_scores, finished, gen_lens, caches = \
                    lax.while_loop(cond, body, state)
                pen = ((5.0 + gen_lens) / 6.0) ** length_penalty
                final = beam_scores / pen
                best = jnp.argmax(final.reshape(b, beam), axis=1)
                pick = jnp.arange(b) * beam + best
                return buf[pick]

            return jax.jit(pure)

        fn = _lru_compiled(jcache, ckey, _build)
        out = fn(p_arrays, b_arrays, input_ids._array, cache_arrays)
        return Tensor._from_array(out)


def speculative_generate(model, draft_model, input_ids, max_new_tokens=20,
                         num_speculative_tokens=4, do_sample=False,
                         temperature=1.0, top_k=None, top_p=None,
                         eos_token_id=None, seed_key=None):
    """Speculative decoding, batched (reference analog: PaddleNLP's
    speculative/draft-model inference; Leviathan et al. 2023).

    The draft model proposes ``num_speculative_tokens`` tokens per round;
    ONE multi-token target forward verifies them (the preallocated-cache
    step builds the correct [b, 1, s, L] mask at per-row positions,
    _update_prealloc_cache), and the accepted prefix plus one
    correction/bonus token is committed per row:

    * greedy (``do_sample=False``): exact-match acceptance against the
      target's argmax — output IDENTICAL to
      ``jit_generate(model, ..., do_sample=False)``; the draft only
      changes how many target forwards are needed.
    * sampling (``do_sample=True``): the standard stochastic rule —
      draft token x accepted with prob ``min(1, p(x)/q(x))`` (p/q the
      temperature/top-k/top-p-FILTERED target/draft distributions, the
      same distributions the direct sampler draws from); on rejection
      the replacement is drawn from ``norm(max(p - q, 0))``, on full
      acceptance the bonus comes from p.  Marginally the output is
      distributed exactly as direct sampling from the target.

    Batch b >= 1: every row keeps its own cache position, acceptance
    length, and finished flag; rows that hit ``eos_token_id`` (or their
    token budget) stop writing while the rest continue.

    TPU-native: the ENTIRE loop (draft scan + verify + acceptance) is one
    jitted lax.while_loop program — no host round-trips per round; cache
    "rewind" after rejection is free (stale entries sit beyond each
    row's pos, masked out and later overwritten).
    """
    from ..framework import random as _random
    from .generation import filter_logits

    k = int(num_speculative_tokens)
    if k < 1:
        raise ValueError("num_speculative_tokens must be >= 1")
    b, prompt_len = input_ids.shape
    total = prompt_len + max_new_tokens

    with _eval_mode(model, draft_model):
        pn_t, p_t, bn_t, b_t, cache_t = _decode_state(model, b,
                                                      total + k + 1)
        pn_d, p_d, bn_d, b_d, cache_d = _decode_state(draft_model, b,
                                                      total + k + 1)
        key = seed_key if seed_key is not None else _random.next_key()

        # the compiled program closes over BOTH modules' structures, so
        # the draft's identity must key the cache too
        ckey = (prompt_len, max_new_tokens, k, b, bool(do_sample),
                float(temperature), top_k, top_p, eos_token_id,
                id(draft_model))
        jcache = model.__dict__.setdefault("_spec_decode_cache", {})

        def _build():
            def _probs(logits):
                """The filtered distribution the direct sampler draws
                from — p and q MUST both be post-filter for the
                accept/residual algebra to target it."""
                return jax.nn.softmax(
                    filter_logits(logits.astype(jnp.float32), temperature,
                                  top_k, top_p), axis=-1)

            def _pick(logits, sub):
                return _sample(logits, sub, do_sample, temperature, top_k,
                               top_p).astype(jnp.int32)

            def pure(p_t_, b_t_, p_d_, b_d_, ids, cache_t, cache_d, key):
                ids = ids.astype(jnp.int32)
                zeros_b = jnp.zeros((b,), jnp.int32)
                t_lg, cache_t = _model_step(model, pn_t, bn_t, p_t_, b_t_,
                                            ids, cache_t, zeros_b)
                _, cache_d = _model_step(draft_model, pn_d, bn_d, p_d_,
                                         b_d_, ids, cache_d, zeros_b)
                key, sub = jax.random.split(key)
                cur = _pick(t_lg[:, -1, :], sub)            # [b]
                fill = eos_token_id if eos_token_id is not None else 0
                buf = jnp.full((b, total + k + 1), fill, jnp.int32)
                buf = lax.dynamic_update_slice(buf, ids, (0, 0))
                buf = buf.at[:, prompt_len].set(cur)
                n = jnp.ones((b,), jnp.int32)
                pos = jnp.full((b,), prompt_len, jnp.int32)
                fin = jnp.zeros((b,), bool)
                if eos_token_id is not None:
                    fin = cur == eos_token_id
                fin = fin | (n >= max_new_tokens)

                def cond(state):
                    return jnp.any(~state[6])

                def body(state):
                    n, buf, cur, pos, cache_t, cache_d, fin, key = state
                    key, kdraft, kacc, krepl = jax.random.split(key, 4)

                    def dstep(carry, sub):
                        tok, cd, dpos = carry
                        lg, cd = _model_step(
                            draft_model, pn_d, bn_d, p_d_, b_d_,
                            tok[:, None], cd, dpos)
                        lg = lg[:, -1, :]
                        nxt = _pick(lg, sub)
                        out = (nxt, _probs(lg)) if do_sample else nxt
                        return (nxt, cd, dpos + 1), out

                    # k+1 draft steps: the last one's PROPOSAL is unused,
                    # but its cache write stores d_k's kv — without it a
                    # fully-accepted round leaves a hole at pos+k that
                    # would silently degrade later draft proposals
                    (_, cache_d, _), outs = lax.scan(
                        dstep, (cur, cache_d, pos),
                        jax.random.split(kdraft, k + 1))
                    if do_sample:
                        props = outs[0][:k].T               # [b, k]
                        qs = jnp.moveaxis(outs[1][:k], 0, 1)  # [b, k, V]
                    else:
                        props = outs[:k].T                  # [b, k]
                    # verify [cur, d1..dk] (k+1 cols) in ONE target
                    # forward so every paid-for proposal is checked;
                    # logits[:, j] chooses the token at each row's
                    # pos + j + 1
                    verify = jnp.concatenate([cur[:, None], props], axis=1)
                    t_lg, cache_t = _model_step(
                        model, pn_t, bn_t, p_t_, b_t_, verify, cache_t,
                        pos)
                    idx = jnp.arange(k + 1)[None, :]        # [1, k+1]
                    if do_sample:
                        ps = _probs(t_lg)                   # [b, k+1, V]
                        take = lambda d, t: jnp.take_along_axis(
                            d, t[..., None], axis=-1)[..., 0]
                        p_tok = take(ps[:, :k, :], props)   # [b, k]
                        q_tok = take(qs, props)             # [b, k]
                        u = jax.random.uniform(kacc, (b, k))
                        acc = (u * q_tok < p_tok).astype(jnp.int32)
                        m = jnp.sum(jnp.cumprod(acc, axis=1), axis=1)
                        # replacement draw at EVERY position: residual
                        # norm(max(p-q,0)) for 0..k-1, bonus p at k; only
                        # the draw at index m is ever committed
                        res = jnp.maximum(ps[:, :k, :] - qs, 0.0)
                        rs = jnp.sum(res, axis=-1, keepdims=True)
                        # p==q makes the residual empty; rejection there
                        # has prob 0, guard the 0/0 with p itself
                        res = jnp.where(rs > 0, res / rs, ps[:, :k, :])
                        cand = jnp.concatenate([res, ps[:, k:, :]], axis=1)
                        repl = jax.random.categorical(
                            krepl, jnp.log(cand + 1e-30),
                            axis=-1).astype(jnp.int32)      # [b, k+1]
                        props_pad = jnp.concatenate(
                            [props, repl[:, -1:]], axis=1)
                        tok_out = jnp.where(idx < m[:, None],
                                            props_pad, repl)
                    else:
                        greedy = jnp.argmax(t_lg, axis=-1).astype(jnp.int32)
                        acc = (props == greedy[:, :k]).astype(jnp.int32)
                        m = jnp.sum(jnp.cumprod(acc, axis=1), axis=1)
                        tok_out = greedy        # [b, k+1]; valid thru m
                    cur_next = jnp.take_along_axis(
                        tok_out, m[:, None], axis=1)[:, 0]
                    emit = m + 1                            # [b], 1..k+1
                    if eos_token_id is not None:
                        hit = (tok_out == eos_token_id) & (idx <= m[:, None])
                        any_hit = jnp.any(hit, axis=1)
                        e = jnp.argmax(hit, axis=1)
                        emit = jnp.where(any_hit,
                                         jnp.minimum(emit, e + 1), emit)
                        # eos-pad the committed window past the first eos
                        tok_out = jnp.where(
                            any_hit[:, None] & (idx > e[:, None]),
                            eos_token_id, tok_out)
                        new_fin = fin | any_hit
                    else:
                        new_fin = fin
                    emit = jnp.where(fin, 0, emit)

                    def row_write(rowbuf, toks, start, f):
                        upd = lax.dynamic_update_slice(rowbuf, toks,
                                                       (start,))
                        return jnp.where(f, rowbuf, upd)

                    buf = jax.vmap(row_write)(buf, tok_out,
                                              prompt_len + n, fin)
                    cur = jnp.where(fin, cur, cur_next)
                    n = n + emit
                    pos = pos + emit
                    new_fin = new_fin | (n >= max_new_tokens)
                    return (n, buf, cur, pos, cache_t, cache_d,
                            new_fin, key)

                state = (n, buf, cur, pos, cache_t, cache_d, fin, key)
                state = lax.while_loop(cond, body, state)
                return state[1][:, :total]

            return jax.jit(pure)

        fn = _lru_compiled(jcache, ckey, _build)
        out = fn(p_t, b_t, p_d, b_d, input_ids._array, cache_t, cache_d,
                 key)
        if eos_token_id is not None:
            out = jnp.asarray(
                _truncate_at_eos(out, prompt_len, eos_token_id))
        return Tensor._from_array(out)
