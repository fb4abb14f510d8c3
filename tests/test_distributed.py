"""Distributed layer: sharding rules (AbstractMesh, no devices needed) and
multi-device integration (subprocesses with xla_force_host_platform_device_count
so the main pytest process stays single-device)."""
import os
import subprocess
import sys
import textwrap

import jax
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import get_config
from repro.distributed.fault import HeartbeatTracker, StragglerPolicy
from repro.distributed.sharding import (
    cache_pspecs,
    param_pspecs,
    tokens_pspec,
    zero_variant,
)

MESH = AbstractMesh((16, 16), ("data", "model"))
MESH3 = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def _specs(arch, mesh=MESH):
    cfg = get_config(arch)
    from repro.models.backbone import init_params

    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    return cfg, params, param_pspecs(params, mesh)


def test_dense_param_rules():
    cfg, params, specs = _specs("yi-34b")
    assert specs["tok"]["embed"] == P("model", None)
    lay = specs["layers"]
    assert lay["attn"]["wq"] == P(None, None, "model")       # stacked (L, D, H*hd)
    assert lay["attn"]["wo"] == P(None, "model", None)
    assert lay["ffn"]["w_gate"] == P(None, None, "model")
    assert lay["ffn"]["w_down"] == P(None, "model", None)
    assert all(e is None for e in lay["norm1"])               # replicated
    # yi-34b kv=8 < 16 shards => replicated kv projections
    assert lay["attn"]["wk"] == P(None, None, None)


def test_moe_expert_parallel_rule():
    cfg, params, specs = _specs("llama4-scout-17b-a16e")
    moe = specs["layers"]["moe"]
    assert moe["w_gate"] == P(None, "data", None, "model")   # (L, E, D, F)
    assert moe["w_down"] == P(None, "data", "model", None)   # (L, E, F, D)
    # qwen2: 60 experts not divisible by 16 -> no EP, TP only
    _, _, specs2 = _specs("qwen2-moe-a2.7b")
    assert specs2["layers"]["moe"]["w_gate"] == P(None, None, None, "model")


def test_rwkv_and_hybrid_rules():
    _, _, specs = _specs("rwkv6-7b")
    tm = specs["layers"]["time_mix"]
    assert tm["wr"] == P(None, None, "model")
    assert tm["wo"] == P(None, "model", None)
    cm = specs["layers"]["channel_mix"]
    assert cm["wv"] == P(None, "model", None)                # rows = hidden
    _, _, hz = _specs("zamba2-2.7b")
    mam = hz["layers"]["mamba"]
    assert mam["w_x"] == P(None, None, "model")
    assert mam["w_b"] == P(None, None, None)                 # small N=64: replicated
    assert mam["out_proj"] == P(None, "model", None)


def test_cache_rules_decode_and_long():
    cfg = get_config("yi-34b")
    from repro.models.backbone import init_cache

    cache = jax.eval_shape(lambda: init_cache(cfg, 128, 32768))
    specs = cache_pspecs(cache, cfg, MESH)
    assert specs["k"] == P(None, ("data",), None, "model", None)
    zcfg = get_config("zamba2-2.7b")
    zcache = jax.eval_shape(lambda: init_cache(zcfg, 1, 524288))
    zspecs = cache_pspecs(zcache, zcfg, MESH)
    assert zspecs["k"] == P(None, None, None, ("data", "model"), None)
    assert zspecs["ssm_state"] == P(None, None, "model", None, None)


def test_zero_variant_rules():
    assert zero_variant(P(None, "model"), (4096, 11008), MESH) == P(("data",), "model")
    # first dim not divisible -> moves to next
    assert zero_variant(P(None, None, "model"), (7, 4096, 512), MESH) == \
        P(None, ("data",), "model")
    # EP'd leaf already uses the data axis -> unchanged
    assert zero_variant(P(None, "data", None, "model"), (48, 16, 5120, 8192), MESH) == \
        P(None, "data", None, "model")


def test_tokens_pspec_multi_pod():
    assert tokens_pspec((256, 4096), MESH3) == P(("pod", "data"), None)
    assert tokens_pspec((1,), MESH3) == P(None)


def test_straggler_policy():
    pol = StragglerPolicy(multiple=3.0, redispatch_overhead_s=1e-3)
    assert pol.mitigate(0.01, 0.01, 0.02) == 0.01            # on time
    # 10x straggler: bounded by deadline + redispatch + backup
    assert pol.mitigate(0.1, 0.01, 0.02) == pytest.approx(0.03 + 1e-3 + 0.02)


def test_heartbeat_tracker():
    hb = HeartbeatTracker(interval_s=1.0, miss_limit=3)
    hb.beat("pool-a", 0.0)
    hb.beat("pool-b", 2.5)
    assert hb.dead(3.1) == ["pool-a"]
    assert set(hb.dead(10.0)) == {"pool-a", "pool-b"}


# ---------------------------------------------------------------------------
# multi-device integration (subprocess keeps pytest single-device)
# ---------------------------------------------------------------------------
def _run_subprocess(body: str) -> str:
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..", "src"))
    script = textwrap.dedent(body)
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=420)
    assert out.returncode == 0, f"stdout={out.stdout}\nstderr={out.stderr}"
    return out.stdout


@pytest.mark.slow
def test_sharded_train_step_8dev():
    out = _run_subprocess("""
        import jax
        from repro.configs import get_reduced_config
        from repro.models import init_params
        from repro.launch.mesh import make_host_mesh
        from repro.training.train_step import make_sharded_train_step
        from repro.training.optimizer import init_opt_state, AdamWConfig
        from repro.training.data import DataPipeline
        cfg = get_reduced_config("yi-6b", num_layers=2, d_model=256, d_ff=512)
        mesh = make_host_mesh(data=2, model=4)
        params = init_params(jax.random.PRNGKey(0), cfg)
        pipe = DataPipeline(cfg, mesh, batch=4, seq=32, seed=0)
        step = make_sharded_train_step(mesh, cfg, params, next(pipe),
                                       AdamWConfig(lr=1e-3), donate=False)
        p, o = params, init_opt_state(params)
        for _ in range(3):
            p, o, m = step(p, o, next(pipe))
            assert float(m["loss"]) == float(m["loss"])  # not NaN
        print("OK", float(m["loss"]))
    """)
    assert "OK" in out


@pytest.mark.slow
def test_compressed_train_step_8dev():
    out = _run_subprocess("""
        import jax
        from repro.configs import get_reduced_config
        from repro.models import init_params
        from repro.launch.mesh import make_host_mesh
        from repro.training.train_step import (
            make_compressed_train_step, init_residual)
        from repro.training.optimizer import init_opt_state, AdamWConfig
        from repro.training.data import DataPipeline
        cfg = get_reduced_config("yi-6b", num_layers=2, d_model=256, d_ff=512)
        mesh = make_host_mesh(data=8, model=1)
        params = init_params(jax.random.PRNGKey(0), cfg)
        step = make_compressed_train_step(mesh, cfg, AdamWConfig(lr=1e-3))
        res = init_residual(params, mesh)
        pipe = DataPipeline(cfg, mesh, batch=8, seq=32, seed=0)
        p, o = params, init_opt_state(params)
        for _ in range(3):
            p, o, res, m = step(p, o, res, next(pipe))
        print("OK", float(m["loss"]))
    """)
    assert "OK" in out


@pytest.mark.slow
def test_elastic_failover_8dev(tmp_path):
    out = _run_subprocess(f"""
        from repro.configs import get_reduced_config
        from repro.training.elastic import ElasticTrainer
        from repro.training.optimizer import AdamWConfig
        cfg = get_reduced_config("yi-6b", num_layers=2, d_model=256, d_ff=512)
        tr = ElasticTrainer(cfg, batch=4, seq=32, ckpt_dir={str(tmp_path)!r},
                            model_axis=2, ckpt_every=4, opt_cfg=AdamWConfig(lr=1e-3))
        hist = tr.run(12, fail_at={{8: 4}})
        assert tr.step == 12, tr.step
        assert dict(tr.mesh.shape)["data"] * dict(tr.mesh.shape)["model"] == 4
        print("OK", tr.step, dict(tr.mesh.shape))
    """)
    assert "OK" in out


@pytest.mark.slow
def test_int8_allreduce_accuracy_8dev():
    out = _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import AbstractMesh, PartitionSpec as P
        from repro.launch.mesh import make_host_mesh
        from repro.distributed.compression import int8_allreduce_mean
        mesh = make_host_mesh(data=8, model=1)
        x = jax.random.normal(jax.random.PRNGKey(0), (8, 128))
        f = jax.jit(jax.shard_map(
            lambda s: int8_allreduce_mean(s[0], "data")[None],
            mesh=mesh, in_specs=P("data"), out_specs=P("data")))
        got = np.asarray(f(x))[0]
        want = np.asarray(x).mean(0)
        rel = np.abs(got - want).max() / (np.abs(want).max() + 1e-9)
        assert rel < 0.02, rel
        print("OK", rel)
    """)
    assert "OK" in out
