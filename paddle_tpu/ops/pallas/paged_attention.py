"""Pallas TPU paged attention — the serving hot path: a decode step's
kernel and a prefill chunk's.

One decode step attends a request's whole context through its block
table: the KV pool lives as [num_blocks, block_size, Hkv, D] arrays and
the kernel streams a row's blocks through VMEM with online softmax,
never materializing a contiguous KV window (the jnp fallback
`paged_attention` in ops/nn_kernels.py gathers; this kernel streams).
CuBridge (arXiv:2605.05023) is the PAPERS.md reference for
reconstructing this class of paged attention kernel; the block table
and the per-row lengths are `PrefetchScalarGridSpec` scalar args, as in
the vLLM/TPU pattern.

The walk is per row and ragged.  The grid is the batch's rows; the
pools stay in HBM (`memory_space=ANY`) and one program walks ITS row's
live context, `cdiv(lens[b], block_size)` blocks and not a column more,
in chunks of `chunk_blocks()` pool blocks: the kernel's own DMAs copy a
chunk's blocks through the table into one of two VMEM buffers while the
other is reduced, and a row's last chunk starts the next row's first.
So the time follows the live blocks of the live rows, not slots x table
columns: a dead slot (length 1) costs one block, a table column past a
row's context nothing (`walked_blocks()` is that count, for the
engine's spans).  On the v5e a 16-token block of 16 heads costs 0.27 us
in a chunk of 8 against 0.41 us copied alone (PERF.md, PR 26).

Shapes: the pool keeps heads in the second-minor position, so a block
`(bs, Hkv, D)` is the matrix [(token, kv head), D] as it lies in the
pool (the wrapper's reshape is a bitcast), lands in VMEM so, D on
lanes, and no block is transposed.  A copied chunk, the matrix [(block,
token, kv head), D], is reduced whole by two matrix products on the MXU
with ALL H query heads, `[H, D] . [D, cols]` and `[2 H, cols] . [cols,
D]`, block-diagonal over the kv heads: a column of another head's kv
head is masked to -inf with the positions past the row's length, so the
MXU does Hkv times the useful multiplies and nothing is re-laid.  bf16
x bf16 products are summed in float32; p stays float32 in effect (its
bfloat16 rounding and the remainder ride the one product against V as
2 H rows), and a float32 pool takes its products at the highest
precision.  The body this replaced in PR 33 scored a block a query head
at a time with two broadcast-multiply-reduces on the VPU; on the v5e,
kernel alone, ms a layer then / now (PERF.md, PR 33): 64 heads over 8
bfloat16, 64 rows, ~6,200 live blocks (the hybrid cell) 2.63 / 0.71;
32 over 8, 32 rows, ~760 blocks 0.245 / 0.194; 16 over 16 (the dense
cells) 0.224 / 0.201; only a float32 pool at ONE query head a kv head,
which no cell serves, lost (0.293 / 0.314).

Lane-aligned head dims only (D % 128 == 0; the pool is the replica's
whole KV memory, so in-call padding would copy it per layer per step):
other head dims keep the XLA gather fallback, whose masked-sdpa math is
the parity reference.  GQA: q head h reads kv head h // (H // Hkv); q
goes in as [B, H, D], the model's own head order.

A PREFILL CHUNK (more than one query row a request) has a kernel of its
own below, `paged_prefill_attention`: the same walk's rule (the first
block under a band, `chunk_blocks`, `walked_blocks`) and another body,
because the needs conflict.  A decode step is bound by the pool's
bytes: few rows, all heads at once, Hkv times the useful multiplies for
free.  A chunk is bound by its products (1,024 rows x 48 heads over
11k positions are 290 GFLOP): a program takes a TILE of query positions
(`prefill_tile`), walks the blocks that tile sees (whole key chunks
above the causal diagonal or left of the band are skipped, not masked)
and reduces a copied chunk A KV HEAD AT A TIME: the g query heads of a
kv head ride as tq x g rows of one left operand against that head's
keys, `[tq g, D] . [D, keys]` and `[tq g, keys] . [keys, D]`, online
softmax in float32 scratch: ONE batched product over the kv heads, so
that a process traces and lowers one step whatever the heads (written
out head by head the kernel read 5% faster and took eight times the
operations; a serving process traces a kernel for every bucket and
layer kind before its first request, and set-up time is an end-to-end
metric).  A kv head's rows lie Hkv apart in the copied chunk;
Mosaic loads 32-bit rows with a stride and refuses 16-bit ones, and
refuses a DMA of one kv head of a block (`k_hbm.at[blk, :, h]`: "Slice
shape along dimension 2 must be aligned to tiling (8), but is 1"), so a
16-bit pool's heads come apart in PAIRS through a 32-bit view of the
buffer: the even head is the low half of each word, the odd one the
high half, and a half moved to the top of the word IS that bfloat16 as
a float32 (shift or mask, bitcast, convert: exact, ~5% of the body's
vector work).  Bodies timed on the v5e at the window-and-full cell's
full layer (PERF.md, PR 35).  The shape chooses the kernel (`supports`):
nothing else does.  The tile's walk (`PrefillWalk`) is shared with the
latent pool's prefill kernel (`latent_paged_attention.py`), whose body
is its own.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = float("-inf")
_LANES = 128
_CHUNK_BYTES = 512 * 1024       # one operand's chunk, as it lies in HBM
_CHUNK_VMEM = 1024 * 1024       # ... and at most, as it lies in VMEM


def band_blocks(window, block_size):
    """The most pool blocks a band of `window` positions lies in."""
    return -(-(int(window) - 1) // block_size) + 1


def chunk_blocks(table_cols, block_size, kv_heads, head_dim, dtype,
                 window=None):
    """Pool blocks the kernel copies per step of its walk (`C`): as
    many as make one operand's copy about half a MiB, so that a step's
    DMAs are worth their set-up, while K and V, double-buffered, stay a
    quarter of the 16 MiB of VMEM a kernel may scope.  The second bound
    counts the head axis padded to whole sublane tiles, as a block
    `(bs, Hkv, D)` lay in VMEM until PR 33; as the matrix [(token, kv
    head), D] it is padded only where bs x Hkv is short of a tile, so
    the bound is kept and spare.  Under a `window` no row walks more
    than the band's blocks, whatever its table holds."""
    if window is not None:
        table_cols = min(table_cols, band_blocks(window, block_size))
    itemsize = jnp.dtype(dtype).itemsize
    sublanes = 8 * 4 // itemsize
    row = block_size * head_dim * itemsize
    in_vmem = row * -(-kv_heads // sublanes) * sublanes
    return max(1, min(table_cols, _CHUNK_BYTES // (row * kv_heads),
                      _CHUNK_VMEM // in_vmem))


def walked_blocks(lens, table_cols, block_size, window=None, queries=1):
    """Pool blocks a kernel copies and reduces for rows whose LAST query
    sees `lens` positions (host numbers; a decode row has one query, a
    prefill chunk `queries` of them, the first at ``len - queries``): a
    row's walk ends with the block that holds its last position (of its
    last chunk only the blocks it lives in are copied, so the chunk does
    not enter), a dead slot (length 1) walks one block, and no row walks
    past its table.  Under a `window` the walk STARTS at the block that
    holds the first position its first query sees,
    ``max(0, len - queries + 1 - window) // block_size``.  (The prefill
    kernel walks a tile of query rows at a time and so meets a block
    once a tile that sees it: this counts each block once.)"""
    total = 0
    for n in lens:
        n = max(int(n), 1)
        first = 0 if window is None \
            else max(n - queries + 1 - window, 0) // block_size
        total += max(min(-(-n // block_size), table_cols) - first, 0)
    return total


def _decode_kernel(tables_ref, lens_ref, q_ref, k_hbm, v_hbm, o_ref,
                   k_buf, v_buf, sems, slot_s, *, bs, chunk, g, scale,
                   window):
    b = pl.program_id(0)
    rows, cols = tables_ref.shape

    def visible(row):
        return jnp.maximum(lens_ref[row], 1)

    def first_block(row):       # the block of the first visible position
        return jnp.maximum(visible(row) - window, 0) // bs

    def blocks(row):            # the rule `walked_blocks` states
        n = jnp.minimum(pl.cdiv(visible(row), bs), cols)
        return n if window is None \
            else jnp.maximum(n - first_block(row), 0)

    def copies(row, i, slot, act):
        """`act` on the copy of every block of the row's i-th chunk
        that the row lives in; returns how many those are."""
        first = i * chunk
        n = jnp.minimum(blocks(row) - first, chunk)
        if window is not None:
            first += first_block(row)

        def one(c, _):
            blk = tables_ref[row, first + c]
            act(pltpu.make_async_copy(
                k_hbm.at[blk], k_buf.at[slot, c], sems.at[0, slot]))
            act(pltpu.make_async_copy(
                v_hbm.at[blk], v_buf.at[slot, c], sems.at[1, slot]))
            return _

        lax.fori_loop(0, n, one, 0)
        return n

    def start(row, i, slot):
        copies(row, i, slot, lambda dma: dma.start())

    @pl.when(b == 0)
    def _first():
        slot_s[0] = 0
        start(0, 0, 0)

    length = visible(b)
    n_chunks = pl.cdiv(blocks(b), chunk)

    # k_buf: (2, chunk, bs * Hkv, D), a chunk the matrix [(block, token,
    # kv head), D]; q: (H, D).  `q . K^T` scores every query head
    # against every kv head's rows; the columns of another head's kv
    # head, and those past the row's length, are -inf before the running
    # max, so their p is exactly 0 and `p . V` sums a head's own rows
    heads, d = q_ref.shape[1:]
    hkv = heads // g
    per_block = bs * hkv
    width = chunk * per_block
    q = q_ref[0]
    exact = k_buf.dtype == jnp.float32
    precision = lax.Precision.HIGHEST if exact else None
    col = lax.broadcasted_iota(jnp.int32, (heads, width), 1)
    own = col % hkv == lax.broadcasted_iota(
        jnp.int32, (heads, width), 0) // g  # column (.., kv head) of a head

    def reduce_chunk(i, carry):
        slot, m_prev, l_prev, acc = carry
        # the next chunk flies while this one is reduced: this row's,
        # or after its last the next row's first
        last = i + 1 == n_chunks
        nxt_row = jnp.where(last, b + 1, b)

        @pl.when(nxt_row < rows)
        def _prefetch():
            start(nxt_row, jnp.where(last, 0, i + 1), 1 - slot)

        n = copies(b, i, slot, lambda dma: dma.wait())

        # blocks of the chunk that no copy wrote hold what the scratch
        # held: their p is 0, and 0 x NaN is NaN in a product
        @pl.when(n < chunk)
        def _clear():
            def one(c, _):
                v_buf[slot, c] = jnp.zeros((per_block, d), v_buf.dtype)
                return _
            lax.fori_loop(n, chunk, one, 0)

        k = k_buf[slot].reshape(width, d)
        v = v_buf[slot].reshape(width, d)
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            precision=precision,
                            preferred_element_type=jnp.float32) * scale
        # the chunk's first column is position `base` of the context
        base = i * chunk * bs
        if window is not None:
            base += first_block(b) * bs
        live = own & (col < (length - base) * hkv)
        if window is not None:      # the band: positions >= len - window
            live &= col >= (length - window - base) * hkv
        s = jnp.where(live, s, _NEG_INF)                    # (H, width)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)                              # masked -> 0
        corr = jnp.exp(m_prev - m_new)
        if exact:
            pv = jnp.dot(p, v, precision=precision,
                         preferred_element_type=jnp.float32)
        else:
            # p keeps 16 bits of its mantissa: its bfloat16 rounding and
            # the rounding's remainder ride ONE product against V as 2 H
            # rows, and the halves are added
            hi = p.astype(v.dtype)
            lo = (p - hi.astype(jnp.float32)).astype(v.dtype)
            pv = jnp.dot(jnp.concatenate([hi, lo], axis=0), v,
                         preferred_element_type=jnp.float32)
            pv = pv[:heads] + pv[heads:]
        return (1 - slot, m_new,
                l_prev * corr + p.sum(axis=1, keepdims=True),
                acc * corr + pv)

    slot, _, l, acc = lax.fori_loop(
        0, n_chunks, reduce_chunk,
        (slot_s[0], jnp.full((heads, 1), _NEG_INF, jnp.float32),
         jnp.zeros((heads, 1), jnp.float32),
         jnp.zeros((heads, d), jnp.float32)))
    slot_s[0] = slot
    o_ref[0] = (acc / l).astype(o_ref.dtype)


def paged_decode_attention(q, k_pool, v_pool, tables, lens, scale=None,
                           interpret=False, window=None):
    """One-token paged attention.  q: [B, 1, H, D]; pools:
    [N, bs, Hkv, D]; tables: [B, M] int32 block ids; lens: [B] int32
    visible context length, INCLUDING the token just written, so at
    least 1: a row of length 0 is walked as a dead slot is, over
    position 0 of its first block, and its output means nothing.
    `scale` is a host number (None: 1 / sqrt(D)), fixed at trace time.
    `window` (a host number): the row sees its last `window` positions
    alone, and its walk starts at the block that holds the first of
    them; the kernel then carries the name
    ``paged_window_decode_attention`` in a device trace.
    Returns [B, 1, H, D] in the q dtype."""
    B, s, H, D = q.shape
    if s != 1:
        raise ValueError("paged_decode_attention is decode-only (s == 1)")
    if D % _LANES:
        # never pad the POOL here — it is the replica's whole KV memory,
        # and an in-call jnp.pad would copy it per layer per step.
        # supports() routes these shapes to the XLA gather fallback.
        raise ValueError(
            f"paged_decode_attention needs head_dim % {_LANES} == 0 "
            f"(got {D}); the XLA fallback serves other head dims")
    scale = float(scale) if scale is not None else 1.0 / (D ** 0.5)
    if window is not None and int(window) < 1:
        raise ValueError(f"window={window} is no band")
    return _paged_decode(q, k_pool, v_pool, tables, lens, scale=scale,
                         interpret=bool(interpret),
                         window=None if window is None else int(window))


# jitted, so that a model's layers trace and lower ONE kernel: the walk
# with its loops and copies costs 0.05 s a call site to trace
@functools.partial(jax.jit,
                   static_argnames=("scale", "interpret", "window"))
def _paged_decode(q, k_pool, v_pool, tables, lens, *, scale, interpret,
                  window):
    B, _, H, D = q.shape
    N, bs, Hkv, _ = k_pool.shape
    M = tables.shape[1]
    g = H // Hkv
    chunk = chunk_blocks(M, bs, Hkv, D, k_pool.dtype, window)
    # a block as the matrix [(token, kv head), D] it already is in
    # memory, and q in the model's own head order
    qb = q[:, 0]
    k_pool = k_pool.reshape(N, bs * Hkv, D)
    v_pool = v_pool.reshape(N, bs * Hkv, D)
    block = (bs * Hkv, D)

    kernel = functools.partial(_decode_kernel, bs=bs, chunk=chunk, g=g,
                               scale=scale, window=window)
    q_spec = pl.BlockSpec(
        (1, H, D), lambda b, tables_ref, lens_ref: (b, 0, 0))
    pool_spec = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[q_spec, pool_spec, pool_spec],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((2, chunk) + block, k_pool.dtype),
            pltpu.VMEM((2, chunk) + block, v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qb.shape, q.dtype),
        # a row hands the next its first chunk in flight: rows in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_decode_attention" if window is None
        else "paged_window_decode_attention",
    )(tables.astype(jnp.int32), lens.astype(jnp.int32),
      qb, k_pool, v_pool)
    return out.reshape(B, 1, H, D)


# ---------------------------------------------------------------- prefill
_TILE_ROWS = 12288          # (query position, head) rows one program holds
_PREFILL_VMEM = 96 << 20    # of the v5e's 128 MiB; the default scope is 16


def prefill_tile(s, heads, rows=None):
    """Query positions one program of the prefill kernel attends: the
    chunk halved until its rows, positions x heads, are `rows` at most
    (`_TILE_ROWS` unless a kernel of wider rows says less), and while
    the halves stay whole 16-row tiles."""
    rows = _TILE_ROWS if rows is None else rows
    tq = int(s)
    while tq * heads > rows and tq % 32 == 0:
        tq //= 2
    return tq


class PrefillWalk:
    """The walk of one program of a prefill kernel, shared by the K/V
    kernel below and the latent pool's (`latent_paged_attention.py`):
    the program (b, t) holds query positions ``p0 .. p1`` of request b
    (tile t of `tq`), and walks the blocks ``first .. first + n_blocks
    - 1`` of its table -- the decode kernel's rule for a row whose first
    query sees p0 + 1 positions and whose last sees p1 + 1 -- in chunks
    of `chunk` blocks, two buffers deep.  What a block's copy is and how
    a copied chunk is reduced are the body's (`run`)."""

    def __init__(self, tables_ref, pos_ref, *, tq, bs, chunk, window):
        self.tables_ref, self.chunk = tables_ref, chunk
        self.b, self.t = pl.program_id(0), pl.program_id(1)
        cols = tables_ref.shape[1]
        self.p0 = pos_ref[self.b] + self.t * tq
        p1 = self.p0 + tq - 1
        self.first = 0 if window is None \
            else jnp.maximum(self.p0 - (window - 1), 0) // bs
        self.n_blocks = jnp.maximum(
            jnp.minimum(pl.cdiv(p1 + 1, bs), cols) - self.first, 0)
        self.n_chunks = pl.cdiv(self.n_blocks, chunk)

    def run(self, copy_block, reduce_chunk, copy_chunk=None):
        """`copy_block(blk, c, slot, start)` starts (or waits for) the
        copy of pool block `blk` into place `c` of buffer `slot`;
        `reduce_chunk(i, slot)` reduces the tile's i-th chunk, copied
        whole into buffer `slot` (of its last chunk only the blocks the
        tile sees: the rest of the buffer holds what it held).
        `copy_chunk(i, slot, start)`, where given, starts (or waits for)
        a copy of the chunk's own beside its blocks', or copies the
        blocks itself where `copy_block` is None."""
        chunk, n_blocks, n_chunks = self.chunk, self.n_blocks, self.n_chunks

        def copies(i, slot, start):
            if copy_chunk is not None:
                copy_chunk(i, slot, start)
            if copy_block is None:
                return
            at = self.first + i * chunk

            def one(c, _):
                copy_block(self.tables_ref[self.b, at + c], c, slot, start)
                return _

            lax.fori_loop(0, jnp.minimum(n_blocks - i * chunk, chunk),
                          one, 0)

        def walk(i, slot):
            # chunk i + 1 flies while chunk i is reduced (i = -1: the first)
            @pl.when(i + 1 < n_chunks)
            def _prefetch():
                copies(i + 1, 1 - slot, True)

            @pl.when(i >= 0)
            def _reduce():
                copies(i, slot, False)
                reduce_chunk(i, slot)

            return 1 - slot

        lax.fori_loop(-1, n_chunks, walk, 1)


def _prefill_kernel(tables_ref, pos_ref, q_ref, k_hbm, v_hbm, *refs,
                    bs, chunk, g, tq, scale, window, picks=False):
    """The body is written in `lax` where `jnp` would do, and reduces
    all kv heads in one batched product: a serving process traces and
    lowers this kernel for every bucket and layer kind before its first
    request, a `jnp` call costs a trace several times a primitive's,
    and set-up time is an end-to-end metric.  With `picks` a query
    attends only the positions whose score (`sc_hbm` [B, s, L] in HBM,
    copied a chunk at a time beside the chunk's blocks) reaches its
    threshold (`tau_ref`, [tq, 1] of the tile)."""
    if picks:
        (sc_hbm, tau_ref, o_ref, kv_buf, sems, m_ref, l_ref, acc_ref,
         sc_buf, sc_sems) = refs
    else:
        o_ref, kv_buf, sems, m_ref, l_ref, acc_ref = refs
    walker = PrefillWalk(tables_ref, pos_ref, tq=tq, bs=bs, chunk=chunk,
                         window=window)
    hkv, rows, _ = q_ref.shape[1:]              # rows: (position, head of g)
    per_block = bs * hkv
    keys = chunk * bs
    dtype = kv_buf.dtype
    exact = dtype == jnp.float32
    precision = lax.Precision.HIGHEST if exact else None
    f32 = jnp.float32
    first = walker.first

    def copy_block(blk, c, slot, start):
        # K and V of a block into one buffer
        dst = pl.ds(pl.multiple_of(c * per_block, per_block), per_block)
        for kv, hbm in enumerate((k_hbm, v_hbm)):
            dma = pltpu.make_async_copy(
                hbm.at[blk], kv_buf.at[slot, kv, dst], sems.at[kv, slot])
            dma.start() if start else dma.wait()

    m_ref[...] = jnp.full(m_ref.shape, _NEG_INF, f32)
    l_ref[...] = jnp.zeros(l_ref.shape, f32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, f32)

    # the position of a query row, and of a chunk's column
    q_pos = walker.p0 + lax.broadcasted_iota(jnp.int32, (rows, keys), 0) // g
    col = lax.broadcasted_iota(jnp.int32, (rows, keys), 1)
    score = (hkv, rows, keys)

    def reduce_chunk(i, slot):
        # a masked column's p is 0, and 0 x NaN is NaN in a product:
        # what no copy wrote, and what the pool holds under a bucket's
        # padding rows, is cleared in V before anything multiplies it
        v = kv_buf[slot, 1]
        sound = lax.le(lax.abs(lax.convert_element_type(v, f32)),
                       jnp.finfo(f32).max)
        kv_buf[slot, 1] = lax.select(sound, v, lax.full_like(v, 0))
        # The chunk is the matrix [(block, token, kv head), D]: a kv
        # head's rows lie hkv apart.  Rows of 32 bits are loaded with a
        # stride (from a host number, so the heads come apart here, one
        # by one); two kv heads share a 32-bit row of a 16-bit pool, the
        # even one its low half: each half, moved to the top of the
        # word, is that bfloat16 as a float32.  K and V go together.
        ref = kv_buf.at[slot]
        if hkv == 1:
            heads = [ref[...]]
        elif exact:
            heads = [ref[:, pl.ds(h, keys, stride=hkv), :]
                     for h in range(hkv)]
        else:
            words = ref.bitcast(jnp.uint32)
            heads = []
            for pair in range(hkv // 2):
                w = words[:, pl.ds(pair, keys, stride=hkv // 2), :]
                heads += [pltpu.bitcast(bits, f32) for bits in (
                    lax.shift_left(w, jnp.uint32(16)),
                    lax.bitwise_and(w, jnp.uint32(0xFFFF0000)))]
        # [K | V, kv head, keys, D]
        kv = lax.convert_element_type(
            lax.concatenate([lax.expand_dims(h, (1,)) for h in heads], 1),
            dtype)
        # the chunk's columns are positions base .. base + keys - 1
        at = lax.add(col, (first + i * chunk) * bs)
        live = lax.le(at, q_pos)
        if window is not None:
            live = lax.bitwise_and(live, lax.gt(at, lax.sub(q_pos, window)))
        if picks:
            # a position's pick, as a row (position, head of g) meets it
            picked = lax.convert_element_type(
                lax.ge(sc_buf[slot], tau_ref[0]), f32)
            picked = lax.reshape(
                lax.broadcast_in_dim(picked, (tq, g, keys), (0, 2)),
                (rows, keys))
            live = lax.bitwise_and(live, lax.gt(picked, f32(0.5)))
        # one step of the online softmax, every kv head's products in
        # one batched product: nothing is written out head by head (a
        # loop over the heads rolled two at a time read 57% slower than
        # this on the v5e, written out 5% faster at eight times the
        # operations to trace and lower)
        s = lax.dot_general(q_ref[0], kv[0], (((2,), (2,)), ((0,), (0,))),
                            precision=precision, preferred_element_type=f32)
        s = lax.select(lax.broadcast_in_dim(live, score, (1, 2)),
                       lax.mul(s, f32(scale)), lax.full(score, _NEG_INF, f32))
        m_prev = m_ref[...]
        m_new = lax.max(m_prev,
                        lax.expand_dims(lax.reduce_max(s, (2,)), (2,)))
        # a row that has seen nothing yet: exp(-inf - 0) is 0
        m_at = lax.select(lax.eq(m_new, _NEG_INF),
                          lax.full_like(m_new, 0), m_new)
        p = lax.exp(lax.sub(s, lax.broadcast_in_dim(m_at, score, (0, 1, 2))))
        corr = lax.exp(lax.sub(m_prev, m_at))               # masked p: 0
        l_ref[...] = lax.add(lax.mul(l_ref[...], corr),
                             lax.expand_dims(lax.reduce_sum(p, (2,)), (2,)))
        acc_ref[...] = lax.add(
            lax.mul(acc_ref[...],
                    lax.broadcast_in_dim(corr, acc_ref.shape, (0, 1, 2))),
            lax.dot_general(lax.convert_element_type(p, dtype), kv[1],
                            (((2,), (1,)), ((0,), (0,))),
                            precision=precision, preferred_element_type=f32))
        m_ref[...] = m_new

    def copy_chunk(i, slot, start):
        dma = pltpu.make_async_copy(
            sc_hbm.at[walker.b, pl.ds(walker.t * tq, tq),
                      pl.ds(pl.multiple_of((first + i * chunk) * bs, keys),
                            keys)],
            sc_buf.at[slot], sc_sems.at[slot])
        dma.start() if start else dma.wait()

    walker.run(copy_block, reduce_chunk, copy_chunk if picks else None)

    o_ref[0] = lax.convert_element_type(
        lax.div(acc_ref[...],
                lax.broadcast_in_dim(l_ref[...], acc_ref.shape, (0, 1, 2))),
        o_ref.dtype)


def paged_prefill_attention(q, k_pool, v_pool, tables, pos, scale=None,
                            interpret=False, window=None, picks=None):
    """Paged attention of a chunk of `s` query rows a request.  q:
    [B, s, H, D]; pools: [N, bs, Hkv, D]; tables: [B, M] int32 block
    ids; pos: [B] int32, the context offset of a row's FIRST query: row
    i sees the absolute positions ``<= pos + i`` (under `window`, a host
    number, those in ``(pos + i - window, pos + i]``), and the chunk's
    own K/V are in the pool already.  A request's walk reads the blocks
    ``max(pos - (window - 1), 0) // bs`` (0 without a window) ..
    ``cdiv(pos + s, bs) - 1`` of its table, clipped to its columns, and
    nothing else: entries before the band may hold any id.  The kernel
    carries the name ``paged_prefill_attention`` (under a window
    ``paged_window_prefill_attention``) in a device trace.  `picks`
    (scores [B, s, L] float32, L whole chunks of the walk and at least
    the table's positions; thresholds [B, s, 1]): row i attends only
    the positions whose score reaches its threshold, and the kernel is
    named ``sparse_prefill_attention``.  Returns [B, s, H, D] in the q
    dtype."""
    D = q.shape[-1]
    if not supports(q.shape, k_pool.shape, q.dtype):
        raise ValueError(
            f"paged_prefill_attention does not serve q {q.shape} over a "
            f"pool {k_pool.shape} of {q.dtype}; the XLA fallback does")
    scale = float(scale) if scale is not None else 1.0 / (D ** 0.5)
    if window is not None and int(window) < 1:
        raise ValueError(f"window={window} is no band")
    return _paged_prefill(q, k_pool, v_pool, tables, pos, picks,
                          scale=scale, interpret=bool(interpret),
                          window=None if window is None else int(window))


# jitted as `_paged_decode` is: a model's layers of one kind and bucket
# trace and lower ONE kernel
@functools.partial(jax.jit,
                   static_argnames=("scale", "interpret", "window"))
def _paged_prefill(q, k_pool, v_pool, tables, pos, picks=None, *, scale,
                   interpret, window):
    B, s, H, D = q.shape
    N, bs, Hkv, _ = k_pool.shape
    M = tables.shape[1]
    g = H // Hkv
    tq = prefill_tile(s, H)
    # a tile under a band sees `window - 1 + tq` positions at most
    chunk = chunk_blocks(M, bs, Hkv, D, k_pool.dtype,
                         None if window is None else window - 1 + tq)
    # the g query heads of a kv head ride as rows (position, head) of
    # one left operand: [B, Hkv, s * g, D]
    qh = q.reshape(B, s, Hkv, g, D).transpose(0, 2, 1, 3, 4) \
        .reshape(B, Hkv, s * g, D)
    k_pool = k_pool.reshape(N, bs * Hkv, D)
    v_pool = v_pool.reshape(N, bs * Hkv, D)
    rows = tq * g

    kernel = functools.partial(_prefill_kernel, bs=bs, chunk=chunk, g=g,
                               tq=tq, scale=scale, window=window,
                               picks=picks is not None)
    q_spec = pl.BlockSpec(
        (1, Hkv, rows, D), lambda b, t, tables_ref, pos_ref: (b, 0, t, 0))
    pool_spec = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [q_spec, pool_spec, pool_spec]
    scratch = [
        # two slots of a chunk's K and V as they lie in the pool
        pltpu.VMEM((2, 2, chunk * bs * Hkv, D), k_pool.dtype),
        pltpu.SemaphoreType.DMA((2, 2)),
        pltpu.VMEM((Hkv, rows, 1), jnp.float32),
        pltpu.VMEM((Hkv, rows, 1), jnp.float32),
        pltpu.VMEM((Hkv, rows, D), jnp.float32),
    ]
    extra, name = (), ("paged_prefill_attention" if window is None
                       else "paged_window_prefill_attention")
    if picks is not None:
        extra, name = picks, "sparse_prefill_attention"
        in_specs += [pool_spec, pl.BlockSpec(
            (1, tq, 1), lambda b, t, tables_ref, pos_ref: (b, t, 0))]
        scratch += [pltpu.VMEM((2, tq, chunk * bs), jnp.float32),
                    pltpu.SemaphoreType.DMA((2,))]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, s // tq),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=scratch,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qh.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_PREFILL_VMEM),
        interpret=interpret,
        name=name,
    )(tables.astype(jnp.int32), pos.astype(jnp.int32), qh, k_pool, v_pool,
      *extra)
    return out.reshape(B, Hkv, s, g, D).transpose(0, 2, 1, 3, 4) \
        .reshape(B, s, H, D)


def supports(q_shape, pool_shape, dtype, mp=1):
    """Shape/dtype gate for the pallas paged path; anything else keeps
    the jnp gather fallback (which is also the numerics reference).
    `mp` is the number of head shards the fleet mesh cuts the call into
    (kv heads must divide, so every GQA group stays on one shard).  One
    query row a request (`q_shape[1] == 1`) is the decode kernel's, more
    are the prefill kernel's: the shape chooses, nothing else does."""
    if len(q_shape) != 4 or q_shape[1] < 1:
        return False
    if dtype not in (jnp.float32, jnp.bfloat16):
        return False        # Mosaic: "Invalid vector type for load" (f16)
    s, H, D = q_shape[1], q_shape[2], q_shape[3]
    Hkv = pool_shape[2]
    if Hkv == 0 or H % Hkv or Hkv % mp:
        return False
    if D % _LANES:
        # lane-aligned head dims only (128: llama-7b/13b, gpt3-6.7B/13B,
        # qwen2-7b ...): padding the POOL per call would copy the whole
        # KV memory every step, so other dims keep the gather fallback
        return False
    if s > 1:
        # a 16-bit pool's kv heads come apart in pairs (or there is
        # one), and a chunk that cannot be halved into tiles has to fit
        # the kernel's VMEM whole (twice `_TILE_ROWS` rows compile)
        local = Hkv // mp
        if dtype != jnp.float32 and local > 1 and local % 2:
            return False
        if prefill_tile(s, H // mp) * (H // mp) > 2 * _TILE_ROWS:
            return False
    return True
