"""Compile-only guard: the served path's kernels at Yi-6B widths, built for
a described (not attached) TPU v5e by the TPU compiler.

Interpret mode never enforces the chip's tiling or fast-memory limits;
this compile does, at no chip time. Nothing runs, so it says nothing
about results or speed. The kernels are called with interpret=False
directly, because the ops wrappers pick interpret mode from the backend,
which here is the CPU. The topology is described inside a fixture of this
file only, so that only the worker given this file loads the TPU library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops as kops
from repro.kernels.decode_attention import decode_attention_grouped
from repro.kernels.flash_attention import flash_attention_hsd
from repro.kernels.paged_attention import (
    paged_decode_attention_grouped,
    paged_prefill_attention_fused,
)
from repro.models import backbone

YI = get_config("yi-6b")
H, KV, D = YI.attn.num_heads, YI.attn.num_kv_heads, YI.attn.head_dim
G = H // KV
BLOCK = 16                 # the engine's default block size
POOL_PAGES = 512 + 1       # the engine's default pool plus its dump page
HBM_BYTES = 16 * 2 ** 30   # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # the compiler logs nowhere
        from jax.experimental import topologies
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler, or it cannot describe one
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent cache
        # but cannot be read back without one: keep the cache out of it
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args, **static):
    lowered = jax.jit(fn, static_argnames=tuple(static)).lower(*args, **static)
    assert "tpu_custom_call" in lowered.as_text()
    return lowered.compile()


def test_paged_decode_compiles(one_chip):
    b, nb = 8, 64
    bf = jnp.bfloat16
    _compile(paged_decode_attention_grouped,
             _sds((b, KV, G, D), bf, one_chip),
             _sds((POOL_PAGES, KV, BLOCK, D), bf, one_chip),
             _sds((POOL_PAGES, KV, BLOCK, D), bf, one_chip),
             _sds((b, nb), jnp.int32, one_chip),
             _sds((b,), jnp.int32, one_chip),
             _sds((b, KV, 1, D), bf, one_chip),
             _sds((b, KV, 1, D), bf, one_chip),
             interpret=False)


@pytest.mark.parametrize("chunk", [37, 128, 256])
def test_paged_prefill_fused_compiles(one_chip, chunk):
    nb = 16
    bf = jnp.bfloat16
    _compile(paged_prefill_attention_fused,
             _sds((KV, chunk * G, D), bf, one_chip),
             _sds((POOL_PAGES, KV, BLOCK, D), bf, one_chip),
             _sds((POOL_PAGES, KV, BLOCK, D), bf, one_chip),
             _sds((nb,), jnp.int32, one_chip),
             _sds((), jnp.int32, one_chip),
             _sds((KV, chunk, D), bf, one_chip),
             _sds((KV, chunk, D), bf, one_chip),
             group=G, interpret=False)


@pytest.mark.parametrize("seq", [37, 512, 2048])
def test_flash_attention_compiles(one_chip, seq):
    blk = kops._pick_block(seq, kops._flash_block_default(D))
    bf = jnp.bfloat16
    _compile(flash_attention_hsd,
             _sds((1, H, seq, D), bf, one_chip),
             _sds((1, KV, seq, D), bf, one_chip),
             _sds((1, KV, seq, D), bf, one_chip),
             causal=True, block_q=blk, block_k=blk, interpret=False)


@pytest.mark.parametrize("seq", [1000, 4096])
def test_decode_attention_compiles(one_chip, seq):
    b = 8
    blk = kops._pick_block(seq, kops._decode_block_default(G, D))
    bf = jnp.bfloat16
    _compile(decode_attention_grouped,
             _sds((b, KV, G, D), bf, one_chip),
             _sds((b, KV, seq, D), bf, one_chip),
             _sds((b, KV, seq, D), bf, one_chip),
             _sds((b,), jnp.int32, one_chip),
             block_k=blk, interpret=False)


def test_init_params_fits_one_chip(one_chip):
    """Jitted init makes Yi-6B's 12.12 GB of bf16 weights in place: its
    output fits one chip's HBM, and it needs no float32 copy of a layer
    stack on the side."""
    key = _sds((2,), jnp.uint32, one_chip)
    mem = backbone.init_params.lower(key, YI).compile().memory_analysis()
    shapes = jax.eval_shape(backbone.init_params, jax.random.PRNGKey(0), YI)
    weights = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(shapes))
    assert weights > 12e9                      # published widths, full depth
    assert weights <= mem.output_size_in_bytes < HBM_BYTES
    assert mem.temp_size_in_bytes < 2 ** 30
