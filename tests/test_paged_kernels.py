"""Paged-attention kernel validation: interpret-mode Pallas and the jnp
twins against the densify-then-softmax oracles in kernels/ref.py, plus
the VMEM-budget and dump-block invariants the engine fast path relies on.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced_config
from repro.kernels import ops, ref, vmem
from repro.kernels.paged_attention import paged_decode_attention_grouped
from repro.serving.kv_cache import PagedKVPool


def _rel_err(a, b):
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return np.max(np.abs(a - b)) / (np.max(np.abs(b)) + 1e-6)


def _rand(rng, *shape, dtype=jnp.bfloat16):
    return jnp.asarray(rng.standard_normal(shape), dtype)


def _decode_case(rng, b, kvh, g, d, bs, nbp, nb, lengths):
    """Random pool pages + per-seq tables covering `lengths` tokens each."""
    kp, vp = _rand(rng, nbp, kvh, bs, d), _rand(rng, nbp, kvh, bs, d)
    # distinct physical pages per sequence, in scrambled order
    perm = rng.permutation(nbp - 1)  # keep the last page free as a dump
    tables = jnp.asarray(perm[: b * nb].reshape(b, nb), jnp.int32)
    q = _rand(rng, b, 1, kvh * g, d)
    kn, vn = _rand(rng, b, 1, kvh, d), _rand(rng, b, 1, kvh, d)
    return q, kp, vp, tables, jnp.asarray(lengths, jnp.int32), kn, vn


@pytest.mark.parametrize("kvh,g", [(2, 1), (2, 4), (1, 8), (8, 7)])
def test_paged_decode_vs_ref_grouped(kvh, g):
    rng = np.random.default_rng(0)
    b, d, bs, nb = 3, 32, 8, 3
    lengths = [5, 17, 23]  # ragged: mid-block, block-aligned+1, last slot
    q, kp, vp, tables, lens, kn, vn = _decode_case(
        rng, b, kvh, g, d, bs, 16, nb, lengths)
    want = ref.paged_decode_attention_ref(
        q.reshape(b, kvh, g, d), kp, vp, tables, lens,
        kn.transpose(0, 2, 1, 3), vn.transpose(0, 2, 1, 3))
    got = ops.paged_decode_attention(
        q, kp[None], vp[None], 0, tables, lens, kn, vn, max_len=24, impl="jnp")
    assert _rel_err(got, want.reshape(b, 1, kvh * g, d)) < 5e-2
    got_pl = ops.paged_decode_attention(
        q, kp[None], vp[None], 0, tables, lens, kn, vn, max_len=24,
        impl="pallas")
    assert _rel_err(got_pl, want.reshape(b, 1, kvh * g, d)) < 5e-2


def _grouped_decode(q, kp, vp, layer, tables, lens, kn, vn, pages, compute_pages):
    """The kernel itself, in interpret mode, with explicit page groups."""
    b, _, h, d = q.shape
    kvh = kp.shape[2]
    out = paged_decode_attention_grouped(
        q.reshape(b, kvh, h // kvh, d), kp, vp, jnp.int32(layer), tables, lens,
        kn.transpose(0, 2, 1, 3), vn.transpose(0, 2, 1, 3),
        pages=pages, compute_pages=compute_pages, interpret=True)
    return out.reshape(b, 1, h, d)


# (pages, compute_pages) against a 7-page table of 8-token pages: groups
# that do not divide the table, several compute blocks per group, and one
# group covering the whole table
@pytest.mark.parametrize("pages,compute_pages", [(2, 1), (3, 3), (4, 2), (8, 4)])
@pytest.mark.parametrize("lengths", [
    [5, 55, 0, 16],    # ragged: 1 page, the whole table, empty, 2 full pages
    [48, 8, 24, 1],    # ends several groups before the widest; exact page ends
    [16, 24, 32, 0],   # lengths on group boundaries (P*bs = 16, 24, 32)
])
def test_paged_decode_page_groups(pages, compute_pages, lengths):
    rng = np.random.default_rng(4)
    b, kvh, g, d, bs, nb = 4, 2, 4, 32, 8, 7
    q, kp, vp, tables, lens, kn, vn = _decode_case(
        rng, b, kvh, g, d, bs, 40, nb, lengths)
    want = ref.paged_decode_attention_ref(
        q.reshape(b, kvh, g, d), kp, vp, tables, lens,
        kn.transpose(0, 2, 1, 3), vn.transpose(0, 2, 1, 3))
    got = _grouped_decode(q, kp[None], vp[None], 0, tables, lens, kn, vn,
                          pages, compute_pages)
    assert _rel_err(got, want.reshape(b, 1, kvh * g, d)) < 1e-2


def test_paged_decode_reads_its_layer_of_the_pool():
    """The kernel takes the whole (L, NBp, KV, bs, D) pool and reads layer
    `layer` alone: poisoning every other layer leaves the output as is."""
    rng = np.random.default_rng(5)
    L, b, kvh, g, d, bs, nbp, nb = 3, 2, 2, 4, 32, 8, 12, 4
    q, _, _, tables, lens, kn, vn = _decode_case(
        rng, b, kvh, g, d, bs, nbp, nb, [13, 30])
    kp, vp = _rand(rng, L, nbp, kvh, bs, d), _rand(rng, L, nbp, kvh, bs, d)
    want = ref.paged_decode_attention_ref(
        q.reshape(b, kvh, g, d), kp[1], vp[1], tables, lens,
        kn.transpose(0, 2, 1, 3), vn.transpose(0, 2, 1, 3))
    others = np.arange(L) != 1
    pk, pv = kp.at[others].set(jnp.nan), vp.at[others].set(jnp.nan)
    for impl in ("jnp", "pallas"):
        base = ops.paged_decode_attention(
            q, kp, vp, jnp.int32(1), tables, lens, kn, vn, max_len=31, impl=impl)
        assert _rel_err(base, want.reshape(b, 1, kvh * g, d)) < 5e-2, impl
        got = ops.paged_decode_attention(
            q, pk, pv, jnp.int32(1), tables, lens, kn, vn, max_len=31, impl=impl)
        assert np.array_equal(np.asarray(got), np.asarray(base)), impl
    got = _grouped_decode(q, pk, pv, 1, tables, lens, kn, vn, 2, 1)
    assert np.array_equal(np.asarray(got), np.asarray(base))


def test_paged_decode_ragged_tail_masked():
    """Garbage in slots past `lengths` (and in the dump page) must be
    unobservable - large-but-finite poison leaves the output unchanged."""
    rng = np.random.default_rng(1)
    b, kvh, g, d, bs, nb = 2, 2, 2, 32, 8, 2
    q, kp, vp, tables, lens, kn, vn = _decode_case(
        rng, b, kvh, g, d, bs, 12, nb, [3, 9])
    kp, vp = kp[None], vp[None]
    base = ops.paged_decode_attention(
        q, kp, vp, 0, tables, lens, kn, vn, max_len=10, impl="jnp")
    # poison every page slot at offset >= 2 of the SECOND table page: for
    # seq 0 (len 3) all of it is past the ragged tail
    pk = kp.at[0, np.asarray(tables)[:, 1], :, 2:].set(1e4)
    pv = vp.at[0, np.asarray(tables)[:, 1], :, 2:].set(-1e4)
    poisoned = ops.paged_decode_attention(
        q, pk, pv, 0, tables, lens, kn, vn, max_len=10, impl="jnp")
    assert _rel_err(poisoned[0], base[0]) < 1e-6  # len 3: slots 16.. unread
    for impl in ("jnp", "pallas"):
        out = ops.paged_decode_attention(
            q, pk, pv, 0, tables, lens, kn, vn, max_len=10, impl=impl)
        assert np.all(np.isfinite(np.asarray(out, np.float32)))


def test_paged_decode_dump_poison_past_tail():
    """The kernel masks scores AND values past each sequence's length, so
    even NaN in the dump page and in the tail page's unused slots leaves
    its output bit for bit as it was."""
    rng = np.random.default_rng(6)
    b, kvh, g, d, bs, nb, nbp = 3, 2, 4, 32, 8, 5, 20
    q, kp, vp, tables, lens, kn, vn = _decode_case(
        rng, b, kvh, g, d, bs, nbp, nb, [3, 20, 33])
    dump = nbp - 1
    tables = jnp.asarray(np.where(
        np.arange(nb)[None, :] * bs < np.asarray(lens)[:, None] + 1,
        np.asarray(tables), dump), jnp.int32)             # dump-padded rows
    pk, pv = kp.at[dump].set(jnp.nan), vp.at[dump].set(jnp.nan)
    for row, ln in enumerate([3, 20, 33]):
        tail = int(np.asarray(tables)[row, ln // bs])
        pk = pk.at[tail, :, ln % bs:].set(jnp.nan)
        pv = pv.at[tail, :, ln % bs:].set(jnp.nan)
    want = ref.paged_decode_attention_ref(
        q.reshape(b, kvh, g, d), kp, vp, tables, lens,
        kn.transpose(0, 2, 1, 3), vn.transpose(0, 2, 1, 3))
    for pages, compute_pages in ((nb, nb), (2, 1)):
        base = _grouped_decode(q, kp[None], vp[None], 0, tables, lens, kn, vn,
                               pages, compute_pages)
        assert _rel_err(base, want.reshape(b, 1, kvh * g, d)) < 1e-2
        got = _grouped_decode(q, pk[None], pv[None], 0, tables, lens, kn, vn,
                              pages, compute_pages)
        assert np.array_equal(np.asarray(got), np.asarray(base))


@pytest.mark.parametrize("group,ctx,c", [(1, 13, 5), (4, 8, 8), (2, 0, 7)])
def test_paged_prefill_vs_ref(group, ctx, c):
    rng = np.random.default_rng(2)
    kvh, d, bs, nbp = 2, 32, 8, 10
    kp, vp = _rand(rng, nbp, kvh, bs, d), _rand(rng, nbp, kvh, bs, d)
    nb = max((ctx + bs - 1) // bs, 1)
    table = jnp.asarray(rng.permutation(nbp - 1)[:nb], jnp.int32)
    q = _rand(rng, 1, c, kvh * group, d)
    ks, vs = _rand(rng, 1, c, kvh, d), _rand(rng, 1, c, kvh, d)
    q_tm = q[0].reshape(c, kvh, group, d).transpose(1, 0, 2, 3).reshape(
        kvh, c * group, d)
    want = ref.paged_prefill_attention_ref(
        q_tm, kp, vp, table, jnp.int32(ctx),
        ks[0].transpose(1, 0, 2), vs[0].transpose(1, 0, 2), group=group)
    want = want.reshape(kvh, c, group, d).transpose(1, 0, 2, 3).reshape(
        1, c, kvh * group, d)
    for impl in ("jnp", "pallas"):
        got = ops.paged_prefill_attention(
            q, kp, vp, table, ctx, ks, vs, impl=impl)
        assert _rel_err(got, want) < 5e-2, impl


def test_paged_decode_shared_prefix_blocks():
    """Two sequences adopting the SAME physical blocks (refcount > 1, the
    prefix-cache hit path) must read identical context through their
    tables - and freeing one must not disturb the other's pages."""
    cfg = get_reduced_config("yi-6b", num_layers=2)
    pool = PagedKVPool(cfg, num_blocks=16, block_size=8)
    pool.allocate(0, 16)  # donor: two full blocks
    rng = np.random.default_rng(3)
    L, KV, D = pool.k.shape[0], pool.k.shape[2], pool.k.shape[4]
    kc = _rand(rng, L, KV, 16, D)
    pool.scatter_chunk(0, kc, kc, 0)
    shared = list(pool.seq(0).block_table)
    a1 = pool.adopt(1, shared, 16)
    a2 = pool.adopt(2, shared, 16)
    assert a1.block_table == a2.block_table == shared
    assert all(pool.block_refs(bid) == 3 for bid in shared)
    t1 = pool.device_tables([1], pool.blocks_needed(17))  # dump-padded tail
    t2 = pool.device_tables([2], pool.blocks_needed(17))
    assert np.array_equal(np.asarray(t1), np.asarray(t2))
    q = _rand(rng, 2, 1, cfg.attn.num_heads, D)
    kn = _rand(rng, 2, 1, KV, D)
    lens = jnp.asarray([16, 16], jnp.int32)
    tables = jnp.concatenate([t1, t2])
    for impl in ("jnp", "pallas"):
        out = ops.paged_decode_attention(
            q, pool.k, pool.v, 0, tables, lens, kn, kn, max_len=17, impl=impl)
        # distinct queries over the same pages give distinct rows
        assert not np.array_equal(np.asarray(out[0]), np.asarray(out[1])), impl
        same_q = ops.paged_decode_attention(
            jnp.concatenate([q[:1]] * 2), pool.k, pool.v, 0, tables, lens,
            jnp.concatenate([kn[:1]] * 2), jnp.concatenate([kn[:1]] * 2),
            max_len=17, impl=impl)
        # identical query + shared physical pages -> bitwise identical rows
        assert np.array_equal(np.asarray(same_q[0]), np.asarray(same_q[1])), impl
        if impl == "jnp":
            twin = out
    assert _rel_err(out, twin) < 5e-2
    pool.free(1)  # drops the shared refs, pages survive for seq 2
    assert all(pool.block_refs(bid) == 2 for bid in shared)
    for impl, before in (("jnp", twin), ("pallas", out)):
        after = ops.paged_decode_attention(
            q[1:], pool.k, pool.v, 0, t2, lens[1:], kn[1:], kn[1:],
            max_len=17, impl=impl)
        assert np.array_equal(np.asarray(after[0]), np.asarray(before[1])), impl


# (kv heads, group): Yi-6B, and the Yi-34B stage's GQA group 7
@pytest.mark.parametrize("kvh,g", [(4, 8), (8, 7)])
def test_paged_vmem_estimates(kvh, g):
    bs, d = 16, 128
    chosen = []
    for nb in range(1, 257):
        pages = vmem.paged_decode_pages(kvh, g, bs, d, nb)
        c = vmem.paged_decode_compute_pages(bs, nb)
        est = vmem.paged_decode_vmem(kvh, g, bs, d, pages, c)
        assert est.fits and est.total_bytes > 0, nb
        assert pages % c == 0, (nb, pages, c)
        # one group covers the table, or the groups are as big as fits
        grown = vmem.paged_decode_vmem(kvh, g, bs, d, pages + c, c)
        assert pages >= nb or not grown.fits, nb
        chosen.append(pages)
    assert chosen == sorted(chosen)               # grows with the table ...
    assert chosen[0] == 1 and chosen[-1] < 256    # ... up to the budget
    est = vmem.paged_prefill_vmem(rows=256, chunk=64, block_size=16,
                                  head_dim=128)
    assert est.fits
    # a pathological chunk must NOT fit, and ops must refuse it loudly
    big = vmem.paged_prefill_vmem(rows=65536, chunk=8192, block_size=16,
                                  head_dim=128)
    assert not big.fits
    with pytest.raises(ValueError, match="VMEM"):
        big.assert_fits("paged_prefill")


def test_autotune_block_defaults_feed_ops():
    """ops' default tile sizes come from vmem.autotune_block and must be
    the largest power-of-two tile that fits the budget."""
    bq = vmem.autotune_block(
        lambda b: vmem.flash_attention_vmem(b, b, 128), lo=16, hi=2048)
    assert bq >= 16 and (bq & (bq - 1)) == 0
    assert not vmem.flash_attention_vmem(bq * 2, bq * 2, 128).fits
    from repro.kernels.ops import _decode_block_default, _flash_block_default
    assert _flash_block_default(128) == bq
    bk = _decode_block_default(8, 128)
    assert vmem.decode_attention_vmem(8, bk, 128).fits
