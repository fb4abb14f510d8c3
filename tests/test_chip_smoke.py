"""chip_smoke.py's phases at reduced size on the CPU, its refusal to run
anywhere but on a TPU, and where the entry points keep the compile cache."""
import importlib.util
import os
import pathlib

import jax
import pytest

from repro.kernels import ops as kops
from repro.launch import serve
from repro.launch.serve import make_model
from repro.models.layers import ExecConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
cs = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(cs)

PROMPT_LEN = 40
MAX_NEW = 4


@pytest.fixture(scope="module")
def model():
    return make_model(cs.ARCH, full=False, seed=0)


@pytest.fixture(params=["platform", "kernels"])
def path(request, monkeypatch):
    """"platform": the engine as this backend picks it (the dense path on
    the CPU). "kernels": steered onto the path a TPU takes - paged, with
    the Pallas kernels, here in interpret mode."""
    if request.param == "kernels":
        monkeypatch.setattr(ExecConfig, "kernels", property(
            lambda self: True if self.use_kernels is None else self.use_kernels))
        resolve = kops.resolve_impl
        monkeypatch.setattr(
            kops, "resolve_impl",
            lambda impl: "pallas" if impl == "auto" else resolve(impl))
    return request.param


def test_refuses_without_tpu(capsys):
    assert jax.devices()[0].platform != "tpu"
    cache_dir = jax.config.jax_compilation_cache_dir
    with pytest.raises(SystemExit) as exc:
        cs.main([])
    assert exc.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out
    assert jax.config.jax_compilation_cache_dir == cache_dir


@pytest.mark.parametrize("kind,batching", [("standalone", "continuous"),
                                           ("standalone", "serialized"),
                                           ("dpd", "continuous")])
def test_phase_serves_every_request(model, path, kind, batching):
    cfg, params = model
    prompts = cs.make_prompts(cfg, 0, length=PROMPT_LEN)
    res = cs.serve(cfg, params, kind, batching, prompts, max_new=MAX_NEW)
    assert res["engine"].paged == (path == "kernels")
    assert res["tokens"] == len(prompts) * MAX_NEW
    assert res["modeled_s"] > 0


def test_repeated_phase_compiles_nothing(model, path):
    cfg, params = model
    prompts = cs.make_prompts(cfg, 1, length=PROMPT_LEN)
    first = cs.serve(cfg, params, "standalone", "continuous", prompts,
                     max_new=MAX_NEW)
    counter = cs.CompileCounter()
    try:
        again = cs.serve(cfg, params, "standalone", "continuous", prompts,
                         max_new=MAX_NEW)
    finally:
        counter.close()
    assert counter.compiles == 0
    assert again["modeled_s"] == first["modeled_s"]


def test_served_logits_match_reference(model, path):
    cfg, params = model
    prompt = cs.make_prompts(cfg, 2, n=1, length=PROMPT_LEN)[0]
    errs = cs.check_logits(cfg, params, prompt)
    assert set(errs) == {"prefill_rel_err", "decode_rel_err"}
    assert all(0 <= e <= cs.LOGITS_RTOL for e in errs.values())


def test_no_tpu_kernels_off_tpu(model):
    """The kernel probe finds none where the backend is no TPU, so its
    positive count on the chip is evidence."""
    cfg, params = model
    engine = serve.build_engine(cfg, params, "standalone")
    assert cs.tpu_kernel_calls(engine, prompt_len=PROMPT_LEN) == {
        "decode": 0, "chunk": 0}


def test_compile_cache_dir(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert serve.enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        got = serve.enable_compile_cache()
        assert got == str(serve.COMPILE_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == got
        assert serve.COMPILE_CACHE_DIR.parent == pathlib.Path(ROOT)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)

