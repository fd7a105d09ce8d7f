"""The rules that decide what runs where: the mesh taken from the devices
present, no silent interpreter fallback, and the compile-cache location."""

import os
import subprocess
import sys

import numpy as np
import pytest

import jax
from jax.sharding import NamedSharding

from repro.ckpt.checkpoint import reblock_params
from repro.configs import get_config
from repro.configs.registry import reduced
from repro.kernels import resolve_kernel_backend
from repro.launch.mesh import device_mesh
from repro.models import params as pm
from repro.models.transformer import param_specs
from repro.runtime import compile_cache
from repro.serve.engine import (EngineConfig, SamplingParams, build_engine,
                                generate)


@pytest.mark.parametrize("n,grid", [(1, (1, 1)), (4, (2, 2)), (16, (4, 4))])
def test_device_mesh_grid_from_device_count(n, grid):
    if len(jax.devices()) < n:
        pytest.skip(f"needs {n} host devices")
    mesh, plan = device_mesh(jax.devices()[:n])
    assert dict(mesh.shape) == {"data": 1, "model": n}
    assert (plan.grid_q, plan.grid_r) == grid
    assert plan.axis_sizes == (1, n)


def test_pallas_backend_refuses_to_interpret_off_tpu():
    assert jax.default_backend() != "tpu"
    with pytest.raises(ValueError, match="pallas-interpret"):
        resolve_kernel_backend("pallas")
    assert resolve_kernel_backend("pallas-interpret") == (True, True)
    assert resolve_kernel_backend("jnp") == (False, False)


@pytest.fixture
def cache_config(monkeypatch):
    """Restore jax's cache directory and the env var around a test."""
    was = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    yield monkeypatch
    jax.config.update("jax_compilation_cache_dir", was)


@pytest.mark.parametrize("env_set", [True, False], ids=["env", "checkout"])
def test_compile_cache_location(cache_config, tmp_path, env_set):
    before = jax.config.jax_compilation_cache_dir
    if env_set:
        cache_config.setenv(compile_cache.ENV, str(tmp_path))
        assert compile_cache.use_compile_cache() == str(tmp_path)
        # jax reads the variable itself: nothing is overridden
        assert jax.config.jax_compilation_cache_dir == before
        return
    first = compile_cache.use_compile_cache()
    assert first == compile_cache.use_compile_cache()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert first == os.path.join(repo, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first


def test_one_by_one_and_two_by_two_grids_agree():
    """The 2x2 grid with the 1x1 grid's weights re-blocked serves the same
    greedy tokens as the 1x1 grid (reduced fp32 qwen2-0.5b)."""
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 host devices")
    cfg = reduced(get_config("qwen2-0.5b"))
    ec = EngineConfig(s_max=64, buckets=(1, 4), block_pos_stride=8,
                      prefill_chunks=(8,))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).tolist()
               for n in (5, 13, 9)]
    sampling = SamplingParams(max_tokens=6)

    mesh1, plan1 = device_mesh(jax.devices()[:1])
    eng1 = build_engine(cfg, mesh1, plan1, engine_cfg=ec, seed=0)
    mesh4, plan4 = device_mesh(jax.devices()[:4])
    specs4 = param_specs(cfg, plan4.grid_q, plan4.grid_r, preskew=False)
    params4 = jax.tree.map(
        lambda a, s: jax.device_put(a, NamedSharding(mesh4, s)),
        reblock_params(eng1.params, eng1.specs, 1, 1, plan4.grid_q,
                       plan4.grid_r),
        pm.param_pspecs(specs4))
    eng4 = build_engine(cfg, mesh4, plan4, engine_cfg=ec, params=params4)

    out1 = generate(eng1, prompts, sampling)
    out4 = generate(eng4, prompts, sampling)
    assert [c.tokens for c in out1] == [c.tokens for c in out4]
    assert all(len(c.tokens) == 6 for c in out1)


def test_importing_the_serving_stack_touches_no_device():
    """A supervising parent imports the whole stack and must stay off the
    devices its worker will own: no module may build an array at import."""
    code = ("import chip_smoke, repro.launch.serve, benchmarks.serve_service\n"
            "from jax._src import xla_bridge\n"
            "print(len(xla_bridge._backends))")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [repo, os.path.join(repo, "src"), os.path.join(repo, "benchmarks")]))
    out = subprocess.run([sys.executable, "-c", code], cwd=repo, env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().splitlines()[-1] == "0"
