"""Launch-layer tests: sharding rule validity + an end-to-end mini dry-run
in a subprocess (its own XLA device-count flag)."""
import json
import subprocess
import sys

import jax
import pytest

from repro.configs import get_config
from repro.launch.input_specs import input_specs, params_struct
from repro.launch.roofline import collective_bytes_from_hlo, _shape_bytes


def test_shape_bytes_parser():
    assert _shape_bytes("bf16[16,128]{1,0}") == 16 * 128 * 2
    assert _shape_bytes("f32[8]") == 32
    assert _shape_bytes("(f32[4,4], bf16[2])") == 64 + 4
    assert _shape_bytes("pred[]") == 1


def test_collective_parser():
    hlo = """
  %ag = bf16[32,64]{1,0} all-gather(bf16[2,64]{1,0} %p), replica_groups={}
  %ar.1 = f32[128]{0} all-reduce(f32[128]{0} %x), to_apply=%sum
  %cp = f32[8]{0} collective-permute(f32[8]{0} %y)
  %add = f32[8]{0} add(f32[8]{0} %a, f32[8]{0} %b)
"""
    got = collective_bytes_from_hlo(hlo)
    assert got["all-gather"] == 32 * 64 * 2
    assert got["all-reduce"] == 128 * 4
    assert got["collective-permute"] == 32
    assert got["all-to-all"] == 0


def test_input_specs_shapes():
    cfg = get_config("pixtral-12b")
    sp = input_specs(cfg, "train_4k")
    # vision prefix is carved out of the sequence budget
    assert sp["tokens"].shape == (256, 4096 - cfg.vision_prefix_len)
    assert sp["embeds"].shape == (256, cfg.vision_prefix_len, cfg.d_model)
    au = input_specs(get_config("musicgen-large"), "decode_32k")
    assert au["tokens"].shape == (128, 4)


def test_params_struct_no_allocation():
    cfg = get_config("qwen2-72b")
    import math
    s = params_struct(cfg)
    total = sum(math.prod(x.shape) for x in jax.tree.leaves(s))
    assert 70e9 < total < 76e9  # 72B params, never materialized


@pytest.mark.slow
def test_mini_dryrun_subprocess():
    """Lower+compile a tiny config on an 8-device (2,4) mesh in a fresh
    subprocess — validates the whole launch path without the 512-device
    cost."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, json
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_config
from repro.launch.sharding import param_shardings, cache_shardings
from repro.launch.steps import build_serve_step
from repro.launch.input_specs import params_struct
from repro.models import LM
mesh = jax.make_mesh((2, 4), ("data", "model"))
cfg = get_config("llama3.2-1b", tiny=True)
model, fn = build_serve_step(cfg)
params_s = params_struct(cfg)
pshard = param_shardings(mesh, params_s, fsdp=False)
cache_s = jax.eval_shape(lambda: LM(cfg).init_cache(8, 64, dtype=cfg.dtype))
cshard = cache_shardings(mesh, cfg, cache_s)
toks = jax.ShapeDtypeStruct((8,), jax.numpy.int32)
pos = jax.ShapeDtypeStruct((8,), jax.numpy.int32)
tshard = NamedSharding(mesh, P("data"))
with jax.set_mesh(mesh):
    compiled = jax.jit(fn, in_shardings=(pshard, cshard, tshard, tshard),
                       out_shardings=(None, None, cshard)).lower(
        params_s, cache_s, toks, pos).compile()
cost = compiled.cost_analysis()
if isinstance(cost, (list, tuple)):
    cost = cost[0]
print(json.dumps({"flops": float(cost.get("flops", 0))}))
"""
    import os
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath("src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=600, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["flops"] > 0


def test_compile_cache_dir_env_wins_else_fixed_in_checkout(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR, when set, is left to JAX; otherwise the
    cache goes to the fixed <repo>/.jax_cache (never a temp or pid path)."""
    from repro.utils import compile_cache as cc
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/jc")
        path, _ = cc.enable_compile_cache()
        assert path == "/elsewhere/jc"
        assert jax.config.jax_compilation_cache_dir == saved[keys[0]]
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path, stats = cc.enable_compile_cache()
        assert path == str(cc.REPO_ROOT / ".jax_cache")
        assert (cc.REPO_ROOT / "chip_smoke.py").exists()
        assert jax.config.jax_compilation_cache_dir == path
        assert (stats.hits, stats.misses) == (0, 0)
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)


def test_chip_of_keys_tpus_by_device_kind():
    from types import SimpleNamespace as Dev
    from repro.utils.hw import TPU_V5E, chip_of
    assert chip_of(Dev(platform="tpu", device_kind="TPU v5 lite")) is TPU_V5E
    assert chip_of(Dev(platform="cpu", device_kind="cpu")) is TPU_V5E
    with pytest.raises(ValueError, match="no peaks for device_kind"):
        chip_of(Dev(platform="tpu", device_kind="TPU v9 imaginary"))
    with pytest.raises(ValueError, match="no peaks for device_kind"):
        chip_of(Dev(platform="gpu", device_kind="NVIDIA A100-SXM4-80GB"))
