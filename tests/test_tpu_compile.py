"""The served Pallas kernels compile for a TPU v5e at Llama-3.2-1B widths.

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
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.kv_append import kv_append
from repro.kernels.kv_quant import page_requant
from repro.kernels.paged_attention import paged_attention
from repro.kernels.ragged_paged_attention import ragged_paged_attention

# Llama-3.2-1B attention: 32 query heads over 8 KV heads of 64, page 16
HKV, G, HD, PAGE = 8, 4, 64, 16
N_PAGES, MAX_PAGES, N_TOK, N_SEQ = 512, 256, 64, 8


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


@pytest.mark.parametrize("pool_dtype", [jnp.bfloat16, jnp.int8])
def test_ragged_paged_attention_compiles(one_chip, pool_dtype):
    s = lambda shape, dt: _spec(one_chip, shape, dt)  # noqa: E731
    i32 = jnp.int32
    pool = s((N_PAGES, PAGE, HKV, HD), pool_dtype)
    quant = pool_dtype == jnp.int8
    scales = [s((N_PAGES, HKV), jnp.float32)] * 2 if quant else []

    def fn(q, k, v, bt, ts, tp, *sc):
        kw = dict(k_scale=sc[0], v_scale=sc[1]) if sc else {}
        return ragged_paged_attention(q, k, v, bt, ts, tp, interpret=False,
                                      **kw)

    hlo = _compile(fn, s((N_TOK, HKV, G, HD), jnp.bfloat16), pool, pool,
                   s((N_SEQ, MAX_PAGES), i32), s((N_TOK,), i32),
                   s((N_TOK,), i32), *scales)
    _assert_kernel(hlo, "ragged_paged_attention")


def test_paged_attention_compiles(one_chip):
    s = lambda shape, dt: _spec(one_chip, shape, dt)  # noqa: E731
    pool = s((N_PAGES, PAGE, HKV, HD), jnp.bfloat16)
    hlo = _compile(
        lambda q, k, v, bt, cl: paged_attention(q, k, v, bt, cl,
                                                interpret=False),
        s((N_SEQ, HKV, G, HD), jnp.bfloat16), pool, pool,
        s((N_SEQ, MAX_PAGES), jnp.int32), s((N_SEQ,), jnp.int32))
    _assert_kernel(hlo, "ragged_paged_attention")


@pytest.mark.parametrize("pool_dtype", [jnp.bfloat16, jnp.int8])
def test_kv_append_compiles(one_chip, pool_dtype):
    s = lambda shape, dt: _spec(one_chip, shape, dt)  # noqa: E731
    pool = s((N_PAGES, PAGE, HKV, HD), pool_dtype)
    rows = s((N_TOK, HKV, HD), pool_dtype)
    ids = s((N_TOK,), jnp.int32)
    hlo = _compile(
        lambda k, v, kn, vn, p, o, va: kv_append(k, v, kn, vn, p, o, va,
                                                 interpret=False),
        pool, pool, rows, rows, ids, ids, ids)
    _assert_kernel(hlo, "kv_append")


def test_page_requant_compiles(one_chip):
    s = lambda shape, dt: _spec(one_chip, shape, dt)  # noqa: E731
    pool = s((N_PAGES, PAGE, HKV, HD), jnp.int8)
    ratio = s((N_TOK, HKV), jnp.float32)
    hlo = _compile(
        lambda k, v, kr, vr, r: page_requant(k, v, kr, vr, r,
                                             interpret=False),
        pool, pool, ratio, ratio, s((N_TOK,), jnp.int32))
    _assert_kernel(hlo, "page_requant")
