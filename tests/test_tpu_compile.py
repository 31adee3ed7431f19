"""The served Pallas kernels compile for a TPU v5e at Llama-3.2-1B widths,
and the ragged attention at the benchmark stages' widths too; the fused
step keeps its stacked KV pools in place across the layer scan.

Interpret mode (every other kernel test) cannot see the TPU compiler's
tiling rules, so a block shape the chip refuses passes there. These tests
lower each served kernel with ``interpret=False`` for a described ``v5e``
chip and compile it — nothing runs, no chip is needed, only the TPU
compiler that ships with libtpu. The topology is described inside a
fixture (only one process at a time may load libtpu, so nothing here may
touch it while modules are imported), and the persistent compilation
cache is off around the compiles (an entry compiled for a described chip
cannot be read back without one).
"""
import functools
import os
import re
from unittest import mock

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import simple_dense
from repro.kernels import ops
from repro.kernels.kv_append import kv_append
from repro.kernels.kv_quant import page_requant
from repro.kernels.ragged_paged_attention import ragged_paged_attention
from repro.models import LM, attention

# Llama-3.2-1B attention: 32 query heads over 8 KV heads of 64, page 16
HKV, G, HD, PAGE = 8, 4, 64, 16
N_PAGES, MAX_PAGES, N_TOK, N_SEQ = 512, 256, 64, 8
# the kernels take the model's pools stacked over a layer group's layers
# and the layer they address
LAYERS = 2


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # no skip: without libtpu's compiler this guard would report green
    # while checking nothing, so a topology that cannot be described fails
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    """Lower and compile for the described chip; returns the HLO text."""
    return jax.jit(fn).lower(*args).compile().as_text()


def _assert_kernel(hlo, name):
    assert "tpu_custom_call" in hlo and name in hlo, \
        f"{name} did not lower to a Mosaic kernel"


# the attention's launch widths: N, Hkv, G, hd, max_pages, n_pages, B —
# Llama-3.2-1B's, and the benchmark's stages at their largest fused-step
# bucket (bench/configs)
WIDTHS = {
    "llama-3.2-1b": (N_TOK, HKV, G, HD, MAX_PAGES, N_PAGES, N_SEQ),
    "deepseek-7b-pp2": (512, 32, 1, 128, 256, 960, 32),
    "qwen2-72b-pp20": (512, 8, 8, 128, 512, 10240, 256),
    # Gemma2-9B: heads wider than one lane width, a window and a softcap
    "gemma2-9b": (256, 8, 2, 256, 256, 512, 32),
    # an odd group with wide heads: the VMEM budget holds T at its floor
    # of 16 rows, T·G = 48 on bf16's sublane tiling
    "odd-group": (512, 32, 3, 256, 256, 960, 32),
}
# (window, softcap) where the widths' model has them
WINDOWS = {"gemma2-9b": (4096, 50.0)}


def _custom_call_names(hlo):
    """Instruction names of the compiled program's Mosaic kernels."""
    return [line.split("=")[0].strip().lstrip("%")
            for line in hlo.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line]


@pytest.mark.parametrize("widths,pool_dtype", [
    pytest.param("llama-3.2-1b", jnp.bfloat16, id="bfloat16"),
    pytest.param("llama-3.2-1b", jnp.int8, id="int8"),
    pytest.param("deepseek-7b-pp2", jnp.bfloat16, id="deepseek-7b-pp2-bf16"),
    pytest.param("deepseek-7b-pp2", jnp.int8, id="deepseek-7b-pp2-int8"),
    pytest.param("qwen2-72b-pp20", jnp.bfloat16, id="qwen2-72b-pp20-bf16"),
    pytest.param("qwen2-72b-pp20", jnp.int8, id="qwen2-72b-pp20-int8"),
    pytest.param("gemma2-9b", jnp.bfloat16, id="gemma2-9b-bf16"),
    pytest.param("gemma2-9b", jnp.int8, id="gemma2-9b-int8"),
    pytest.param("odd-group", jnp.bfloat16, id="odd-group-bf16"),
])
def test_ragged_paged_attention_compiles(one_chip, widths, pool_dtype):
    """The tiled launch compiles, bf16 and int8 pools; every Mosaic kernel
    of the program keeps the name the benchmark's trace reducer finds it
    by."""
    N, Hkv, G_, hd, max_pages, n_pages, B = WIDTHS[widths]
    window, softcap = WINDOWS.get(widths, (None, None))
    s = lambda shape, dt: _spec(one_chip, shape, dt)  # noqa: E731
    i32 = jnp.int32
    pool = s((LAYERS, n_pages, PAGE, Hkv, hd), pool_dtype)
    quant = pool_dtype == jnp.int8
    scales = [s((LAYERS, n_pages, Hkv), jnp.float32)] * 2 if quant else []

    def fn(q, k, v, layer, bt, ts, tp, *sc):
        kw = dict(k_scale=sc[0], v_scale=sc[1]) if sc else {}
        return ragged_paged_attention(q, k, v, layer, bt, ts, tp,
                                      window=window, softcap=softcap,
                                      interpret=False, **kw)

    hlo = _compile(fn, s((N, Hkv, G_, hd), jnp.bfloat16), pool, pool,
                   s((), i32), s((B, max_pages), i32), s((N,), i32),
                   s((N,), i32), *scales)
    _assert_kernel(hlo, "ragged_paged_attention")
    names = _custom_call_names(hlo)
    assert all(n.split(".")[0] == "ragged_paged_attention" for n in names), \
        names


def test_paged_attention_compiles(one_chip):
    """The ragged kernel at a decode-only launch: one token per sequence,
    N = B rows."""
    s = lambda shape, dt: _spec(one_chip, shape, dt)  # noqa: E731
    pool = s((LAYERS, N_PAGES, PAGE, HKV, HD), jnp.bfloat16)
    rows = s((N_SEQ,), jnp.int32)
    hlo = _compile(
        lambda q, k, v, lyr, bt, ts, tp: ragged_paged_attention(
            q, k, v, lyr, bt, ts, tp, interpret=False),
        s((N_SEQ, HKV, G, HD), jnp.bfloat16), pool, pool, s((), jnp.int32),
        s((N_SEQ, MAX_PAGES), jnp.int32), rows, rows)
    _assert_kernel(hlo, "ragged_paged_attention")


@pytest.mark.parametrize("pool_dtype", [jnp.bfloat16, jnp.int8])
def test_kv_append_compiles(one_chip, pool_dtype):
    s = lambda shape, dt: _spec(one_chip, shape, dt)  # noqa: E731
    pool = s((LAYERS, N_PAGES, PAGE, HKV, HD), pool_dtype)
    rows = s((N_TOK, HKV, HD), pool_dtype)
    ids = s((N_TOK,), jnp.int32)
    hlo = _compile(
        lambda k, v, lyr, kn, vn, p, o, va: kv_append(
            k, v, lyr, kn, vn, p, o, va, interpret=False),
        pool, pool, s((), jnp.int32), rows, rows, ids, ids, ids)
    _assert_kernel(hlo, "kv_append")


def test_page_requant_compiles(one_chip):
    s = lambda shape, dt: _spec(one_chip, shape, dt)  # noqa: E731
    pool = s((LAYERS, N_PAGES, PAGE, HKV, HD), jnp.int8)
    ratio = s((N_TOK, HKV), jnp.float32)
    hlo = _compile(
        lambda k, v, lyr, kr, vr, r: page_requant(k, v, lyr, kr, vr, r,
                                                  interpret=False),
        pool, pool, s((), jnp.int32), ratio, ratio, s((N_TOK,), jnp.int32))
    _assert_kernel(hlo, "page_requant")


# the fused step at a benchmark stage's widths with two layers: d, heads,
# KV heads, head size, d_ff, vocabulary, QKV bias; and the pool's pages.
# A pool's layer is given more bytes than the step's other buffers take
# (a layer's weights are read in place; its largest projection is the one
# slice the step holds), so a pool-sized temporary cannot hide under the
# bound.
STEPS = {
    "qwen2-72b-pp20": (8192, 64, 8, 128, 29568, 38016, True, 8192),
    "deepseek-7b-pp2": (4096, 32, 32, 128, 11008, 102400, False, 960),
}
# an instruction and the dimensions of its (array) result
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?(\S+)\s*=\s*\w+\[([\d,]*)\]\S*"
                    r"\s+([\w-]+)\(")


@pytest.mark.parametrize("stage,pool_dtype", [
    pytest.param("qwen2-72b-pp20", None, id="qwen2-72b-pp20-bf16"),
    pytest.param("qwen2-72b-pp20", "int8", id="qwen2-72b-pp20-int8"),
    pytest.param("deepseek-7b-pp2", None, id="deepseek-7b-pp2-bf16"),
])
def test_fused_step_keeps_pools_in_place(one_chip, stage, pool_dtype):
    """The fused step (``forward_mixed_paged``, Pallas kernels) carries the
    stacked pools through the layer scan: no slice, update or copy of a
    layer's pool or of the stack is compiled, and the step's temporaries
    stay below one layer's K + V pool."""
    d, H, Hkv, hd, F, V, bias, n_pages = STEPS[stage]
    cfg = simple_dense(stage, "v5e compile", n_layers=2, d_model=d,
                       n_heads=H, n_kv_heads=Hkv, head_dim=hd, d_ff=F,
                       vocab_size=V, qkv_bias=bias, rope_theta=1e6)
    m = LM(cfg)
    N, B, max_pages = 64, 8, 512

    def spec(tree):
        return jax.tree.map(lambda a: _spec(one_chip, a.shape, a.dtype),
                            tree)
    params = spec(jax.eval_shape(
        lambda: m.init(jax.random.PRNGKey(0), dtype=jnp.bfloat16)))
    pools = spec(jax.eval_shape(
        lambda: m.init_cache(n_pages, PAGE, dtype=jnp.bfloat16,
                             kv_dtype=pool_dtype)))
    i32 = lambda *shape: _spec(one_chip, shape, jnp.int32)  # noqa: E731
    step = jax.jit(
        lambda p, t, ts, tp, ql, pl, bt: m.forward_mixed_paged(
            p, t, ts, tp, ql, pl, bt, discard_pid=n_pages - 1),
        donate_argnums=(5,))
    # the TPU's path, compiled here: the Pallas kernels, not interpreted
    chip = [mock.patch.object(attention, "_paged_use_pallas", lambda: True)]
    chip += [mock.patch.object(ops, name, functools.partial(
        getattr(ops, name), interpret=False)) for name in
        ("kv_append_op", "kv_append_quant_op", "ragged_paged_attention_op")]
    with chip[0], chip[1], chip[2], chip[3]:
        compiled = step.lower(params, i32(N), i32(N), i32(N), i32(B), pools,
                              i32(B, max_pages)).compile()
    hlo = compiled.as_text()
    _assert_kernel(hlo, "ragged_paged_attention")
    _assert_kernel(hlo, "kv_append")

    layer = (n_pages, PAGE, Hkv, hd)
    pool_dims = {layer, (2,) + layer, (n_pages, PAGE * Hkv, hd),
                 (2, n_pages, PAGE * Hkv, hd)}
    moved = []
    for line in hlo.splitlines():
        hit = _INSTR.match(line)
        if hit and hit.group(3) in ("dynamic-slice", "dynamic-update-slice",
                                    "copy", "fusion"):
            dims = tuple(int(x) for x in hit.group(2).split(",") if x)
            if dims in pool_dims:
                moved.append(hit.group(1))
    assert not moved, f"pool-sized ops in the fused step: {moved}"
    item = jax.tree.leaves(pools)[0].dtype.itemsize
    layer_kv = 2 * n_pages * PAGE * Hkv * hd * item
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < layer_kv, (temp, layer_kv)
