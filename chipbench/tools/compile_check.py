"""Compile a configuration's served step programs for a described v5e chip.

    JAX_PLATFORMS=cpu python3 chipbench/tools/compile_check.py --config yi-34b-8l

Nothing runs: the TPU compiler builds the paged decode step
(`serve_step_paged`) at the configuration's `max_batch` and the longest
table, and the prefill chunk (`prefill_chunk_paged`) at `chunk_tokens`
after the longest context, both over the pool the configuration states,
and prints each program's `memory_analysis`. A program that does not fit
the chip, or a kernel the chip's compiler refuses, fails here at no chip
time. The kernels are steered out of interpret mode for the compile.
"""
from __future__ import annotations

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--config", required=True)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from chipbench.lib import registry
    from chipbench.lib.harness import program_config
    from chipbench.lib.weights import dims_of, make_weights, seed_key
    from repro.configs import get_config
    from repro.kernels import ops
    from repro.models import backbone
    from repro.models.layers import ExecConfig

    jax.config.update("jax_enable_compilation_cache", False)
    conf = registry.config(registry.benchmark(), args.config)
    m, e = conf["model"], conf["engine"]
    cfg = program_config(conf, get_config)
    chip = SingleDeviceSharding(
        topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    params = jax.tree.map(lambda x: sds(x.shape, x.dtype),
                          jax.eval_shape(make_weights, seed_key(0), dims_of(m)))
    bs = e["block_size"]
    pool = sds((m["num_hidden_layers"], e["pool_blocks"] + 1, m["num_key_value_heads"], bs,
                m["head_dim"]), jnp.bfloat16)
    nb = m["max_position_embeddings"] // bs
    b = e["max_batch"]
    ex = ExecConfig(use_kernels=True)
    ops._interpret = lambda: False
    i32 = jnp.int32
    decode = backbone._serve_step_paged.lower(
        params, pool, pool, sds((b, nb), i32), sds((b,), i32), sds((b,), i32),
        cfg, ex, 0, "pallas").compile()
    c = e["chunk_tokens"]
    chunk = backbone._prefill_chunk_traced_ctx.lower(
        params, pool, pool, sds((nb - c // bs,), i32), sds((), i32), sds((c,), i32),
        cfg, ex, "pallas").compile()
    from repro.serving.kv_cache import _append_slots
    L, kvh, hd = m["num_hidden_layers"], m["num_key_value_heads"], m["head_dim"]
    append_b = _append_slots.lower(pool, pool, sds((L, b, kvh, hd), jnp.bfloat16),
                                   sds((L, b, kvh, hd), jnp.bfloat16),
                                   sds((b,), i32), sds((b,), i32)).compile()
    append_c = _append_slots.lower(pool, pool, sds((L, c, kvh, hd), jnp.bfloat16),
                                   sds((L, c, kvh, hd), jnp.bfloat16),
                                   sds((c,), i32), sds((c,), i32)).compile()
    for name, prog in (("decode", decode), ("chunk", chunk), ("append_decode", append_b),
                       ("append_chunk", append_c)):
        ma = prog.memory_analysis()
        print(f"{args.config} {name}: arguments {ma.argument_size_in_bytes} B, "
              f"outputs {ma.output_size_in_bytes} B, temporaries {ma.temp_size_in_bytes} B, "
              f"code {ma.generated_code_size_in_bytes} B, "
              f"tpu_custom_call {prog.as_text().count('tpu_custom_call')}")


if __name__ == "__main__":
    main()
