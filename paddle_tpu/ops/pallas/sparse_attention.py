"""Learned sparse attention over a GQA pool on TPU (DeepSeek-V3.2's
lightning indexer in front of GQA): Pallas kernels of the indexer's
scores over a row's live blocks and of a prefill tile's threshold, and
a decode step's attention over the picked positions alone.  A prefill
chunk's attention is the K/V prefill kernel's
walk with a selection test (`paged_attention.paged_prefill_attention`'s
`picks`).

**The indexer** (`indexer_scores`): one key a position, ``k^I`` [W]
(the pool's `ik` plane, [N, bs, W], W whole lanes), against the `h`
indexer queries of a position: a program walks the blocks its queries
see (`paged_attention.PrefillWalk`: a decode row is a tile of one
position) in chunks of `index_chunk` blocks, scores a copied chunk by
ONE product ``[(position, head), W] . [W, keys]``, and sums ``w . relu``
over the heads, float32.  A chunk's blocks that lie side by side in the
pool go in one DMA: XLA plans the copies from the tables
(`_walk_plan`), and the core's loop then turns once a copy, not once a
block (a turn costs ~35-45 ns on a v5e whatever it issues, and a walk
that turned once a block was bound by it).  It writes [B, s, L] scores,
-inf past what a query sees: a decode row's whole row lies in VMEM (named
``indexer_decode_scores`` in a device trace), a prefill tile's chunks go
out by DMA into scores that start at -inf (``indexer_prefill_scores``).

**A decode step's attention** (`picked_attention`): XLA's top-k of the
scores names the picked positions, and XLA gathers THOSE K and V rows
alone (``block * bs + offset``) and attends over them.  So a step reads
``min(ctx + 1, topk)`` positions' K and V a row a layer, not ``ctx``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .paged_attention import PrefillWalk

_NEG_INF = float("-inf")
_LANES = 128
_INDEX_ROWS = 2048      # (position, indexer head) rows a prefill program holds
_INDEX_KEYS = 512       # positions an indexer chunk scores
THRESHOLD_ROWS = 8      # score rows a threshold program holds
_VMEM = 64 << 20        # of the v5e's 128 MiB, for one indexer program


def index_chunk(block_size):
    """Pool blocks of the `ik` plane an indexer chunk copies."""
    return max(1, _INDEX_KEYS // block_size)


def scored_len(table_cols, block_size):
    """Columns of the indexer's scores: the table's positions, padded to
    whole chunks."""
    keys = index_chunk(block_size) * block_size
    return -(-table_cols * block_size // keys) * keys


def index_tile(s, heads):
    """Query positions one program of the prefill indexer scores."""
    tq = int(s)
    while tq * heads > _INDEX_ROWS and tq % 16 == 0:
        tq //= 2
    return tq


def supports(q_shape, pool_shape, ik_shape, dtype):
    """Shape gate of the kernels: whole-lane head and indexer widths, a
    16-bit or 32-bit pool, and a prefill chunk of whole 8-row tiles."""
    if dtype not in (jnp.float32, jnp.bfloat16):
        return False
    s, D, W = q_shape[1], q_shape[3], ik_shape[-1]
    if D % _LANES or W % _LANES or q_shape[2] % pool_shape[2]:
        return False
    return s == 1 or s % 8 == 0


_ID_BITS = 26           # a packed table entry: block id below, piece above


def _piece_sizes(chunk):
    """The static sizes, in blocks, of the walk's copies: the powers of
    two up to `chunk`."""
    return [1 << k for k in range(chunk.bit_length())]


def _walk_plan(tables, chunk):
    """The walk's copies, planned in XLA beside its tables: each entry
    of `tables` [B, M] with, above its `_ID_BITS` of block id, 1 + log2
    of the size of the copy that starts there, or 0 where none starts.
    A copy starts at each maximal run of ascending ids inside an aligned
    chunk of `chunk` columns, and again after each piece of it: a run of
    r blocks is copied in the pieces of r's binary form, largest first
    (`walk_copies` is the rule on the host)."""
    B, M = tables.shape
    col = lax.broadcasted_iota(jnp.int32, (B, M), 1)
    after = jnp.concatenate(
        [jnp.ones((B, 1), bool), tables[:, 1:] != tables[:, :-1] + 1], 1)
    cut = (col % chunk == 0) | after
    start = lax.cummax(jnp.where(cut, col, 0), axis=1)
    nxt = jnp.concatenate([jnp.where(cut, col, M)[:, 1:],
                           jnp.full((B, 1), M, jnp.int32)], 1)
    end = lax.cummin(nxt, axis=1, reverse=True)
    # at a piece's first column what is left of the run, `left`, is the
    # run's length with its larger pieces taken off: r's low bits
    left = end - col
    bits = 32 - lax.clz(left)
    first = ((end - start) & ((1 << bits) - 1)) == left
    return tables | jnp.where(first, bits << _ID_BITS, 0)


def _indexer_kernel(plan_ref, pos_ref, q_ref, w_ref, ik_hbm, *refs, bs,
                    chunk, tq, heads):
    if tq == 1:
        o_ref, buf, sems, counts = refs
    else:
        # the -inf start, aliased
        _, o_hbm, buf, sems, counts, obuf, osem = refs
    walker = PrefillWalk(plan_ref, pos_ref, tq=tq, bs=bs, chunk=chunk,
                         window=None)
    keys = chunk * bs
    f32 = jnp.float32
    precision = lax.Precision.HIGHEST if buf.dtype == f32 else None
    q, w = q_ref[0], w_ref[0]           # [(position, head), W], [.., 1]
    at = lax.broadcasted_iota(jnp.int32, (tq, keys), 1)
    seen = walker.p0 + lax.broadcasted_iota(jnp.int32, (tq, keys), 0)
    if tq == 1:
        o_ref[...] = jnp.full(o_ref.shape, _NEG_INF, f32)
    sizes = _piece_sizes(chunk)

    def piece(size, src, dst, slot):
        return pltpu.make_async_copy(ik_hbm.at[pl.ds(src, size)],
                                     buf.at[slot, pl.ds(dst, size)],
                                     sems.at[slot])

    def copy_chunk(i, slot, start):
        """The chunk's copies by the plan (`_walk_plan`), one a piece; a
        piece that passes the blocks this program walks is cut to them,
        in the pieces of what is left.  `counts[slot]` keeps how many of
        each size, and the chunk's wait takes the same pieces."""
        if not start:
            for k, size in enumerate(sizes):
                def wait(_, carry, size=size):
                    piece(size, 0, 0, slot).wait()
                    return carry
                lax.fori_loop(0, counts[slot, k], wait, 0)
            return
        for k in range(len(sizes)):
            counts[slot, k] = 0
        first = walker.first + i * chunk
        n = jnp.minimum(walker.n_blocks - i * chunk, chunk)

        def copy(c):
            entry = plan_ref[walker.b, first + c]
            blk = entry & ((1 << _ID_BITS) - 1)
            take = jnp.minimum(
                lax.shift_left(1, lax.shift_right_logical(entry, _ID_BITS)
                               - 1), n - c)
            for k, size in reversed(list(enumerate(sizes))):
                @pl.when(take & size != 0)
                def _piece(k=k, size=size):
                    off = take & -(2 * size)        # the larger pieces'
                    piece(size, blk + off, c + off, slot).start()
                    counts[slot, k] += 1

            return c + take

        lax.while_loop(lambda c: c < n, copy, 0)

    def reduce_chunk(i, slot):
        k = buf[slot].reshape(keys, buf.shape[-1])
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            precision=precision, preferred_element_type=f32)
        s = (jnp.maximum(s, 0.0) * w).reshape(tq, heads, keys).sum(axis=1)
        base = i * keys
        # what no copy wrote, and positions a query does not see, are -inf
        s = jnp.where(at + base <= seen, s, _NEG_INF)
        cols = pl.ds(pl.multiple_of(base, keys), keys)
        if tq == 1:
            o_ref[0, :, cols] = s
        else:
            obuf[...] = s
            dma = pltpu.make_async_copy(
                obuf, o_hbm.at[walker.b, pl.ds(walker.t * tq, tq),
                               cols], osem.at[0])
            dma.start()
            dma.wait()

    walker.run(None, reduce_chunk, copy_chunk)


def walk_copies(tables, pos, block_size, queries=1, heads=1):
    """The DMAs the indexer's walk issues for one call (host numbers):
    `tables` [B, M] and `pos` [B] as the call is sent, `queries` query
    positions a row (a decode row 1, a prefill chunk its bucket) and
    `heads` indexer heads, which set the prefill tile.  The kernel's own
    rule: each program walks its row's blocks up to the one that holds
    its last query's position, in aligned chunks of `index_chunk`
    blocks, and copies each maximal run of ascending ids inside a chunk
    in power-of-two pieces, so a run of r blocks costs popcount(r)
    DMAs."""
    pos = np.asarray(pos, np.int64)
    B = len(pos)
    bs, chunk = int(block_size), index_chunk(block_size)
    tq = 1 if queries == 1 else index_tile(queries, heads)
    # blocks each program walks: [B, programs]
    ends = pos[:, None] + tq * np.arange(1, queries // tq + 1)
    n = np.minimum(-(-ends // bs), np.shape(tables)[1])
    cols = int(n.max())
    t = np.asarray(tables)[:, :cols]
    # the runs, over the rows laid end to end: a row and a chunk start
    # one; none starts past what the row's programs walk
    cut = np.empty(t.shape, bool)
    cut[:, 1:] = t[:, 1:] != t[:, :-1] + 1
    cut[:, ::chunk] = True
    for r, walked in enumerate(n.max(axis=1)):
        cut[r, walked:] = False
    starts = np.flatnonzero(cut)
    before = np.zeros(len(starts) + 1, np.int64)
    np.cumsum(np.bitwise_count(np.diff(starts, append=t.size)),
              out=before[1:])
    row = np.arange(B)[:, None] * cols
    last = row + n - 1
    k = np.searchsorted(starts, last, side="right") - 1
    k0 = np.searchsorted(starts, row, side="right") - 1
    return int((before[k] - before[k0]
                + np.bitwise_count(last + 1 - starts[k])).sum())


def indexer_scores(q_idx, w_idx, ik_pool, tables, pos, interpret=False):
    """Lightning-indexer scores over a paged key plane.  q_idx
    [B, s, h, W] (the indexer's queries, W whole lanes), w_idx [B, s, h]
    (their weights), ik_pool [N, bs, W], tables [B, M] int32, pos [B]
    int32: query row i of request b is position ``pos[b] + i`` and sees
    the positions up to it.  Returns [B, s, scored_len(M, bs)] float32,
    ``sum_j w_j relu(q_j . k)`` where a query sees the position and -inf
    elsewhere."""
    return _indexer(q_idx, w_idx, ik_pool, tables, pos,
                    interpret=bool(interpret))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _indexer(q_idx, w_idx, ik_pool, tables, pos, *, interpret):
    B, s, h, W = q_idx.shape
    N, bs, _ = ik_pool.shape
    chunk = index_chunk(bs)
    L = scored_len(tables.shape[1], bs)
    q = q_idx.reshape(B, s * h, W).astype(ik_pool.dtype)
    w = w_idx.reshape(B, s * h, 1).astype(jnp.float32)
    tq = 1 if s == 1 else index_tile(s, h)
    rows = tq * h
    kernel = functools.partial(_indexer_kernel, bs=bs, chunk=chunk, tq=tq,
                               heads=h)
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [pl.BlockSpec((1, rows, W), lambda b, t, *_: (b, t, 0)),
                pl.BlockSpec((1, rows, 1), lambda b, t, *_: (b, t, 0)),
                any_spec]
    if N >= 1 << _ID_BITS:
        raise ValueError(f"a pool of {N} blocks: the walk's plan packs "
                         f"block ids in {_ID_BITS} bits")
    scratch = [pltpu.VMEM((2, chunk, bs, W), ik_pool.dtype),
               pltpu.SemaphoreType.DMA((2,)),
               pltpu.SMEM((2, len(_piece_sizes(chunk))), jnp.int32)]
    args = [_walk_plan(tables.astype(jnp.int32), chunk),
            pos.astype(jnp.int32), q, w, ik_pool]
    out_shape = jax.ShapeDtypeStruct((B, s, L), jnp.float32)
    if s == 1:
        out_spec, aliases, name = (
            pl.BlockSpec((1, 1, L), lambda b, t, *_: (b, 0, 0)), {},
            "indexer_decode_scores")
    else:
        in_specs.append(any_spec)
        args.append(jnp.full((B, s, L), _NEG_INF, jnp.float32))
        scratch += [pltpu.VMEM((tq, chunk * bs), jnp.float32),
                    pltpu.SemaphoreType.DMA((1,))]
        out_spec, aliases, name = any_spec, {5: 0}, "indexer_prefill_scores"
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B, s // tq), in_specs=in_specs,
            out_specs=out_spec, scratch_shapes=scratch),
        out_shape=out_shape,
        input_output_aliases=aliases,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM),
        interpret=interpret,
        name=name,
    )(*args)


def _threshold_kernel(n_ref, x_ref, o_ref, key_ref, *, k, cols):
    """The `k`-th largest of each of the tile's rows over its first
    `n_ref[tile]` columns (what its queries see), exactly: the largest
    key t with at least k keys >= t, found a bit at a time from the sign
    down, over float32 bits mapped to int32 keys that sort as the floats
    do.  The keys stay in VMEM for the 32 counting passes."""
    n = n_ref[pl.program_id(0)]
    chunks = pl.cdiv(n, cols)
    rows = x_ref.shape[0]
    low = jnp.iinfo(jnp.int32).min

    def to_key(c, _):
        at = pl.ds(pl.multiple_of(c * cols, cols), cols)
        bits = pltpu.bitcast(x_ref[:, at], jnp.int32)
        key = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)
        col = c * cols + lax.broadcasted_iota(jnp.int32, (rows, cols), 1)
        key_ref[:, at] = jnp.where(col < n, key, low)
        return _

    lax.fori_loop(0, chunks, to_key, 0)

    def count(cand):
        def one(c, acc):
            at = pl.ds(pl.multiple_of(c * cols, cols), cols)
            return acc + (key_ref[:, at] >= cand).astype(jnp.int32)
        acc = lax.fori_loop(0, chunks, one,
                            jnp.zeros((rows, cols), jnp.int32))
        return acc.sum(axis=1, keepdims=True)

    ans = jnp.where(count(jnp.zeros((rows, 1), jnp.int32)) >= k, 0, low)

    def bit(b, ans):
        cand = ans | lax.shift_left(jnp.int32(1), 30 - b)
        return jnp.where(count(cand) >= k, cand, ans)

    ans = lax.fori_loop(0, 31, bit, ans.astype(jnp.int32))
    out = pltpu.bitcast(jnp.where(ans < 0, ans ^ jnp.int32(0x7FFFFFFF), ans),
                        jnp.float32)
    # a tile that sees fewer than k columns: every score of it is picked
    o_ref[...] = jnp.where(ans == low, _NEG_INF, out)


def topk_threshold(scores, seen, k, interpret=False):
    """[R, 1] float32: the `k`-th largest of each row of `scores`
    [R, L] float32 (R whole tiles of `THRESHOLD_ROWS`), -inf where a
    row holds fewer than k finite scores; `seen` [R // 8] int32 bounds
    the columns a tile's rows can hold a finite score in (the rest are
    -inf and never read).  What XLA's `top_k` gives as its k-th value,
    without its sort: `lax.top_k` of a chunk's [2,048, 51,200] scores
    takes ~137 ms on a v5e.  Named ``sparse_topk_threshold`` in a device
    trace."""
    return _threshold(scores, seen, k=int(k), interpret=bool(interpret))


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def _threshold(scores, seen, *, k, interpret):
    R, L = scores.shape
    rows = THRESHOLD_ROWS
    cols = min(_LANES * 8, L)
    return pl.pallas_call(
        functools.partial(_threshold_kernel, k=k, cols=cols),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(R // rows,),
            in_specs=[pl.BlockSpec((rows, L), lambda r, n: (r, 0))],
            out_specs=pl.BlockSpec((rows, 1), lambda r, n: (r, 0)),
            scratch_shapes=[pltpu.VMEM((rows, L), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((R, 1), jnp.float32),
        interpret=interpret, name="sparse_topk_threshold",
    )(seen.astype(jnp.int32), scores)


def picked_attention(q, k_pool, v_pool, rows, counts, scale=None):
    """One query a request over the picked positions alone, in XLA: a
    gather of the picks' K and V rows and a softmax over them, a kv head
    to its group of query heads.  q [B, 1, H, D];
    pools [N, bs, Hkv, D]; rows [B, K] int32 the pool rows (``block * bs
    + offset``) of the picks, the first `counts` [B] of a row real.
    Rounds as `nn_kernels.sdpa_k` does.  Returns [B, 1, H, D]."""
    B, _, H, D = q.shape
    N, bs, Hkv, _ = k_pool.shape
    scale = float(scale) if scale is not None else 1.0 / (D ** 0.5)
    real = jnp.arange(rows.shape[1]) < counts[:, None]          # [B, K]
    K = k_pool.reshape(N * bs, Hkv, D)[rows]                  # [B, K, Hkv, D]
    # a pick past a row's length points anywhere: 0 x NaN is NaN
    V = jnp.where(real[..., None, None], v_pool.reshape(N * bs, Hkv, D)[rows],
                  0)
    qg = q[:, 0].reshape(B, Hkv, H // Hkv, D)
    s = (jnp.einsum("bngd,bknd->bngk", qg, K) * scale).astype(jnp.float32)
    p = jax.nn.softmax(jnp.where(real[:, None, None], s, _NEG_INF), axis=-1)
    o = jnp.einsum("bngk,bknd->bngd", p.astype(q.dtype), V)
    return o.reshape(B, 1, H, D)
