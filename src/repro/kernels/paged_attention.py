"""Pallas TPU paged attention: gather-free decode + fused chunked prefill.

Both kernels consume the serving layer's `PagedKVPool` storage DIRECTLY,
through int32 block tables, instead of a densified contiguous cache, so
the O(B*S*L) gather/scatter round-trip of the dense engine path
disappears.

paged_decode_attention_grouped
    One query token per sequence attends over its paged prefix. The K/V
    operands are the pool's whole (L, NBp, KV, bs, D) arrays, left in HBM;
    the layer index, lengths and tables are scalar-prefetched. Grid (B,),
    run in order: each program owns one sequence and all its kv heads, and
    copies the pages its length covers by manual DMA, P pages a group
    (one DMA per page and side moves every kv head), double-buffered in
    two VMEM slots: group g + 1 - or, in the last group, the next
    sequence's group 0 - is in flight while group g is scored. P comes
    from the shapes (`vmem.paged_decode_pages`). Groups are scored
    `compute_pages` pages at a time with fp32 online-softmax state per kv
    head, for all G = H/KV grouped query heads (the GQA reuse win, as in
    decode_attention.py); scores and values at or past the length are
    masked (`kpos < len`), so stale slot rows are never observed and
    dump-padded table entries are never read. The new token's K/V is
    NOT yet in the pool - it is passed separately and merged into the
    running softmax last, so the pool write-back shrinks to one slot per
    layer (`PagedKVPool.scatter_append`).

paged_prefill_attention_fused
    One prefill chunk (C tokens of a single sequence) attends over the
    sequence's prior paged context AND itself causally - the hybrid
    chunked-prefill step of the continuous scheduler. Grid (KV, NB + 1):
    the first NB steps stream context pages, one a step through a
    BlockSpec index map that reads the scalar-prefetched table (fully
    visible to every chunk row, ragged tail masked); the final step merges the chunk's own K/V
    with the causal intra-chunk mask and normalizes. Query rows are laid
    out token-major per kv head ((C*G, D), row r is token r // G), so one
    score matrix covers the whole grouped-query chunk.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------
def _paged_decode_kernel(
    layer_ref,                    # scalar-prefetch: (1,) pool layer
    len_ref,                      # scalar-prefetch: (B,) cached lengths
    tbl_ref,                      # scalar-prefetch: (B, NB) block tables
    q_ref,                        # (1, KV, G, D)
    kn_ref, vn_ref,               # (1, KV, 1, D) - the step's new K/V
    k_hbm, v_hbm,                 # (L, NBp, KV, bs, D) - the whole pool, in HBM
    o_ref,                        # (1, KV, G, D)
    k_buf, v_buf,                 # (2, KV, P, bs, D) - two slots of P pages
    sems,                         # DMA semaphores (2, 2): [K/V, slot]
    slot_ref,                     # SMEM (1,): slot of this sequence's group 0
    m_scr, l_scr, acc_scr,        # (KV, G, 1), (KV, G, 1), (KV, G, D)
    *,
    block_size: int,
    pages: int,
    compute_pages: int,
    sm_scale: float,
):
    b = pl.program_id(0)
    has_next = b + 1 < pl.num_programs(0)
    kvh = q_ref.shape[1]
    d = q_ref.shape[3]
    layer = layer_ref[0]
    span = compute_pages * block_size

    def held_pages(bi, g):
        """Pool pages of sequence bi's group g: P, or fewer in its last."""
        return jnp.minimum(pages, pl.cdiv(len_ref[bi], block_size) - g * pages)

    def page_copies(bi, g, slot, i):
        page = tbl_ref[bi, g * pages + i]
        return [pltpu.make_async_copy(src.at[layer, page], dst.at[slot, :, i],
                                      sems.at[j, slot])
                for j, (src, dst) in enumerate(((k_hbm, k_buf), (v_hbm, v_buf)))]

    def start(bi, g, slot):
        def one(i, c):
            for cp in page_copies(bi, g, slot, i):
                cp.start()
            return c
        jax.lax.fori_loop(0, held_pages(bi, g), one, 0)

    def wait(bi, g, slot):
        def one(i, c):
            for cp in page_copies(bi, g, slot, i):
                cp.wait()
            return c
        jax.lax.fori_loop(0, held_pages(bi, g), one, 0)

    # the first program fetches its own group 0; every program fetches the
    # next sequence's group 0 while it computes its own last group
    @pl.when(b == 0)
    def first():
        slot_ref[0] = 0
        start(0, 0, 0)

    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)
    cur = len_ref[b]
    slot0 = slot_ref[0]
    n_groups = pl.cdiv(cur, pages * block_size)

    def group(g, c):
        slot = (slot0 + g) % 2

        @pl.when(g + 1 < n_groups)
        def prefetch_own():
            start(b, g + 1, 1 - slot)

        @pl.when((g + 1 == n_groups) & has_next)
        def prefetch_next():
            start(b + 1, 0, 1 - slot)

        wait(b, g, slot)

        def block(j, c2):
            page0 = j * compute_pages
            kpos0 = (g * pages + page0) * block_size
            for h in range(kvh):
                q = q_ref[0, h]                                  # (G, D)
                k = k_buf[slot, h, pl.ds(page0, compute_pages)].reshape(span, d)
                v = v_buf[slot, h, pl.ds(page0, compute_pages)].reshape(span, d)
                s = jax.lax.dot_general(
                    q, k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * sm_scale  # (G, span)
                kpos = kpos0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
                s = jnp.where(kpos < cur, s, NEG_INF)            # ragged tail mask
                vpos = kpos0 + jax.lax.broadcasted_iota(jnp.int32, (span, d), 0)
                v = jnp.where(vpos < cur, v.astype(jnp.float32), 0.0)
                m_prev = m_scr[h]
                m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
                p = jnp.exp(s - m_new)
                alpha = jnp.exp(m_prev - m_new)
                l_scr[h] = l_scr[h] * alpha + jnp.sum(p, axis=1, keepdims=True)
                acc_scr[h] = acc_scr[h] * alpha + jax.lax.dot_general(
                    p, v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                m_scr[h] = m_new
            return c2

        jax.lax.fori_loop(0, pl.cdiv(held_pages(b, g), compute_pages), block, 0)
        return c

    jax.lax.fori_loop(0, n_groups, group, 0)

    @pl.when((n_groups == 0) & has_next)
    def prefetch_next_empty():
        start(b + 1, 0, slot0)

    slot_ref[0] = (slot0 + n_groups) % 2

    # merge the current token's self-attention term (its K/V is not in the
    # pool yet - scatter_append writes it after the step)
    for h in range(kvh):
        q = q_ref[0, h].astype(jnp.float32)                     # (G, D)
        kn = kn_ref[0, h].astype(jnp.float32)                   # (1, D)
        vn = vn_ref[0, h].astype(jnp.float32)
        s_self = jax.lax.dot_general(
            q, kn, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale                                             # (G, 1)
        m_prev = m_scr[h]
        m_new = jnp.maximum(m_prev, s_self)
        p = jnp.exp(s_self - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l = l_scr[h] * alpha + p
        acc = acc_scr[h] * alpha + p * vn
        o_ref[0, h] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("pages", "compute_pages", "interpret"))
def paged_decode_attention_grouped(
    q: jax.Array,        # (B, KV, G, D) - query heads grouped by kv head
    k_pages: jax.Array,  # (L, NBp, KV, bs, D) - the whole pool storage
    v_pages: jax.Array,
    layer: jax.Array,    # () int32 - the layer of the pool to read
    tables: jax.Array,   # (B, NB) int32 physical page ids (dump-padded)
    lengths: jax.Array,  # (B,) int32 cached tokens (new token sits at this index)
    k_new: jax.Array,    # (B, KV, 1, D) - this step's K/V (post-RoPE)
    v_new: jax.Array,
    *,
    pages: int,          # pages per DMA group (vmem.paged_decode_pages)
    compute_pages: int,  # pages scored at once; divides `pages`
    interpret: bool = False,
) -> jax.Array:
    b, kvh, g, d = q.shape
    block_size = k_pages.shape[3]
    assert tables.shape[1] >= 1, "tables must cover at least one page"
    assert pages % compute_pages == 0, (pages, compute_pages)

    kernel = functools.partial(
        _paged_decode_kernel, block_size=block_size, pages=pages,
        compute_pages=compute_pages, sm_scale=d ** -0.5)
    seq = lambda bi, layer_r, lens, tbl: (bi, 0, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, kvh, g, d), seq),
            pl.BlockSpec((1, kvh, 1, d), seq),
            pl.BlockSpec((1, kvh, 1, d), seq),
            # the pool stays in HBM: the kernel DMAs the pages each
            # sequence's table names, P at a time
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, kvh, g, d), seq),
        scratch_shapes=[
            pltpu.VMEM((2, kvh, pages, block_size, d), k_pages.dtype),
            pltpu.VMEM((2, kvh, pages, block_size, d), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((kvh, g, 1), jnp.float32),
            pltpu.VMEM((kvh, g, 1), jnp.float32),
            pltpu.VMEM((kvh, g, d), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, g, d), q.dtype),
        # a program prefetches the next sequence's pages: the grid runs in order
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), lengths.astype(jnp.int32),
      tables.astype(jnp.int32), q, k_new, v_new, k_pages, v_pages)


# ---------------------------------------------------------------------------
# fused chunked prefill
# ---------------------------------------------------------------------------
def _paged_prefill_kernel(
    ctx_ref,                      # scalar-prefetch: (1,) cached context length
    tbl_ref,                      # scalar-prefetch: (NB,) block table
    q_ref,                        # (1, CG, D) - chunk queries, token-major
    ks_ref, vs_ref,               # (1, C, D)  - the chunk's own K/V
    k_ref, v_ref,                 # (1, 1, bs, D) - one physical context page
    o_ref,                        # (1, CG, D)
    m_scr, l_scr, acc_scr,        # (CG, 1), (CG, 1), (CG, D)
    *,
    block_size: int,
    sm_scale: float,
    nb: int,
    group: int,
):
    ik = pl.program_id(1)

    @pl.when(ik == 0)
    def init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    ctx = ctx_ref[0]
    # context pages: fully visible to every chunk row (they precede the
    # chunk), ragged tail masked
    @pl.when((ik < nb) & (ik * block_size < ctx))
    def compute_ctx():
        q = q_ref[0].astype(jnp.float32)               # (CG, D)
        k = k_ref[0, 0].astype(jnp.float32)            # (bs, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale                                    # (CG, bs)
        kpos = ik * block_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos < ctx, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[...] = m_new

    @pl.when(ik == nb)
    def finalize():
        # intra-chunk causal self-attention: row r is token r // group,
        # column c is chunk token c; visible iff c <= r // group
        q = q_ref[0].astype(jnp.float32)               # (CG, D)
        ks = ks_ref[0].astype(jnp.float32)             # (C, D)
        vs = vs_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, ks, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale                                    # (CG, C)
        row_tok = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // group
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(col <= row_tok, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc = acc_scr[...] * alpha + jax.lax.dot_general(
            p, vs, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("group", "interpret"))
def paged_prefill_attention_fused(
    q: jax.Array,        # (KV, C*G, D) - token-major grouped queries
    k_pages: jax.Array,  # (NBp, KV, bs, D) - ONE layer of the pool storage
    v_pages: jax.Array,
    table: jax.Array,    # (NB,) int32 physical page ids (dump-padded, NB >= 1)
    ctx: jax.Array,      # () or (1,) int32 cached context tokens
    k_self: jax.Array,   # (KV, C, D) - the chunk's own K/V (post-RoPE)
    v_self: jax.Array,
    group: int = 1,
    interpret: bool = False,
) -> jax.Array:
    kvh, cg, d = q.shape
    c = k_self.shape[1]
    assert cg == c * group, (cg, c, group)
    block_size = k_pages.shape[2]
    nb = table.shape[0]
    assert nb >= 1, "pass a dump-padded single-page table when ctx == 0"

    kernel = functools.partial(
        _paged_prefill_kernel, block_size=block_size, sm_scale=d ** -0.5,
        nb=nb, group=group,
    )
    # page fetch on the final (self) step replays the last table entry;
    # the body never reads it
    page_ix = lambda hi, ik, ctx_r, tbl: (tbl[jnp.minimum(ik, nb - 1)], hi, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(kvh, nb + 1),
        in_specs=[
            pl.BlockSpec((1, cg, d), lambda hi, ik, ctx_r, tbl: (hi, 0, 0)),
            pl.BlockSpec((1, c, d), lambda hi, ik, ctx_r, tbl: (hi, 0, 0)),
            pl.BlockSpec((1, c, d), lambda hi, ik, ctx_r, tbl: (hi, 0, 0)),
            pl.BlockSpec((1, 1, block_size, d), page_ix),
            pl.BlockSpec((1, 1, block_size, d), page_ix),
        ],
        out_specs=pl.BlockSpec((1, cg, d), lambda hi, ik, ctx_r, tbl: (hi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((cg, 1), jnp.float32),
            pltpu.VMEM((cg, 1), jnp.float32),
            pltpu.VMEM((cg, d), jnp.float32),
        ],
    )
    ctx_arr = jnp.reshape(ctx, (1,)).astype(jnp.int32)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((kvh, cg, d), q.dtype),
        interpret=interpret,
    )(ctx_arr, table.astype(jnp.int32), q, k_self, v_self, k_pages, v_pages)
