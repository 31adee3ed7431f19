"""Set-up of a run: the model configuration, its weights, the engine under
test and the warm-up of every program the cell's traffic can run.

The weights are the benchmark's, not the program's: one jitted call makes
them on the device from the seed, in bfloat16 (the type they are served
in), laid out as the program's parameter tree. The engine is built around
them in place of its own random init, as a deployment loads a checkpoint.
The plain reference (``bench/reference``) reads the same arrays, so it
takes nothing that the program made.
"""
from __future__ import annotations

import math
from typing import List, Tuple
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np

# weight scales: unit-RMS residual inputs, attention scores with a standard
# deviation of ~2 (peaked, as in trained models), logits of std ~2
QK_GAIN = 1.5
NORM_STD = 0.1
BIAS_STD = 0.1
HEAD_GAIN = 2.0


def model_config(conf: dict, name: str):
    """The program's ModelConfig for a configuration file (HF keys)."""
    from repro.configs.base import simple_dense
    return simple_dense(
        name, conf["source"],
        n_layers=int(conf["num_hidden_layers"]),
        d_model=int(conf["hidden_size"]),
        n_heads=int(conf["num_attention_heads"]),
        n_kv_heads=int(conf["num_key_value_heads"]),
        head_dim=int(conf["head_dim"]),
        d_ff=int(conf["intermediate_size"]),
        vocab_size=int(conf["vocab_size"]),
        qkv_bias=bool(conf["attention_bias"]),
        rope_theta=float(conf["rope_theta"]),
        norm_eps=float(conf["rms_norm_eps"]),
        tie_embeddings=bool(conf["tie_word_embeddings"]))


def seed_key(seed: int):
    """A PRNG key from a seed of any size (seeds may exceed 32 bits)."""
    key = jax.random.PRNGKey(int(seed) & 0x7FFFFFFF)
    return jax.random.fold_in(key, (int(seed) >> 31) & 0x7FFFFFFF)


def make_weights(conf: dict, seed: int, dtype=jnp.bfloat16):
    """The whole parameter tree in one jitted call on the default device.

    Layout (the program's): embed (V, d); per layer, stacked over L:
    norm1/norm2 (d,) holding g - 1 for the RMSNorm gain g; wq (d, H, hd),
    wk/wv (d, Hkv, hd), wo (H, hd, d), optional bq/bk/bv; w_gate/w_up
    (d, F), w_down (F, d); final_norm (d,); lm_head (d, V)."""
    L = int(conf["num_hidden_layers"])
    d = int(conf["hidden_size"])
    H = int(conf["num_attention_heads"])
    Hkv = int(conf["num_key_value_heads"])
    hd = int(conf["head_dim"])
    F = int(conf["intermediate_size"])
    V = int(conf["vocab_size"])
    bias = bool(conf["attention_bias"])

    def build(key):
        ks = iter(jax.random.split(key, 16))

        def normal(shape, std):
            return (jax.random.normal(next(ks), shape, dtype)
                    * jnp.asarray(std, dtype))
        attn = {"wq": normal((L, d, H, hd), QK_GAIN / math.sqrt(d)),
                "wk": normal((L, d, Hkv, hd), QK_GAIN / math.sqrt(d)),
                "wv": normal((L, d, Hkv, hd), 1.0 / math.sqrt(d)),
                "wo": normal((L, H, hd, d), 1.0 / math.sqrt(H * hd))}
        if bias:
            attn.update(bq=normal((L, H, hd), BIAS_STD),
                        bk=normal((L, Hkv, hd), BIAS_STD),
                        bv=normal((L, Hkv, hd), BIAS_STD))
        layer = {"norm1": normal((L, d), NORM_STD), "attn": attn,
                 "norm2": normal((L, d), NORM_STD),
                 "mlp": {"w_gate": normal((L, d, F), 1.0 / math.sqrt(d)),
                         "w_up": normal((L, d, F), 1.0 / math.sqrt(d)),
                         "w_down": normal((L, F, d), 1.0 / math.sqrt(F))}}
        return {"embed": normal((V, d), 1.0),
                "final_norm": normal((d,), NORM_STD),
                "groups": ({"scan": {"b0": layer}},),
                "lm_head": normal((d, V), HEAD_GAIN / math.sqrt(d))}
    return jax.jit(build)(seed_key(seed))


def build_engine(cfg, params, serving: dict, policy: str = "infercept"):
    """The engine under test (the InferCept policy unless told otherwise),
    fused + overlapped step, bf16 weights and KV, around the benchmark's
    weights. ``serving["host_kv_tokens"]``, where it is not null, is the
    host memory the deployment gives swapped-out KV, in tokens (null: the
    scheduler's own default)."""
    from repro.core import POLICIES
    from repro.models import LM
    from repro.serving.engine import Engine
    with mock.patch.object(LM, "init", lambda self, key, dtype=None: params):
        eng = Engine(cfg, POLICIES[policy],
                     page_size=int(serving["page_size"]),
                     n_pages=int(serving["n_pages"]),
                     max_model_len=int(serving["max_model_len"]),
                     seed=0, dtype=jnp.bfloat16, kv_dtype=None,
                     fused=True, overlap=True)
    host = serving.get("host_kv_tokens")
    if host is not None:
        _need(eng.sched, "cpu_capacity")
        eng.sched.cpu_capacity = int(host)
    return eng


def _need(obj, name: str):
    """The attribute the harness sets up or warms through; a program that
    no longer has it fails the run rather than skip the step."""
    if not hasattr(obj, name):
        raise AttributeError(f"{type(obj).__name__} has no {name!r}; the "
                             f"benchmark's set-up needs it")
    return getattr(obj, name)


def _pow2(lo: int, hi: int) -> List[int]:
    out, b = [], 1
    while b <= hi:
        if b >= lo:
            out.append(b)
        b *= 2
    return out


def fused_buckets(max_batch: int, max_tokens: int) -> List[Tuple[int, int]]:
    """Every (sequences, tokens) bucket pair the fused step can be called
    with: powers of two, tokens >= sequences."""
    return [(b, n) for b in _pow2(1, max_batch) for n in _pow2(b, max_tokens)]


def max_swap_pages(eng, warm: dict) -> int:
    """The most pages one request can move in one iteration: the swap
    budget of the longest iteration the cost model can plan (a full token
    budget over a full pool), page-aligned, plus a partial page; 0 where
    the deployment gives swapped KV no host memory."""
    if _need(eng.sched, "cpu_capacity") <= 0:
        return 0
    pool_tokens = eng.blocks.n_pages * eng.page
    budget = eng.cost.swap_tokens_within(
        eng.cost.t_fwd(int(warm["max_tokens"]), pool_tokens))
    return min(eng.max_pages, -(-budget // eng.page) + 1)


def warm_programs(eng, warm: dict) -> dict:
    """Compile (or load from the persistent cache) and run once every
    program the window can call: the fused step at each bucket pair, and
    the swap gather/scatter at each page count. Padded rows write only to
    the engine's write-discard page."""
    n_fused = n_swap = 0
    mixed = _need(eng, "_mixed_jit")
    scratch = _need(eng, "scratch_page")
    for B, N in fused_buckets(int(warm["max_batch"]),
                              int(warm["max_tokens"])):
        # host int64 arrays converted as the engine converts them, so the
        # conversions' programs are warm too
        i32 = lambda a: jnp.asarray(a, jnp.int32)  # noqa: E731
        zeros = np.zeros(N, np.int64)
        sampled, _, eng.pools = mixed(
            eng.params, i32(zeros), i32(zeros),
            i32(np.full(N, -1, np.int64)), i32(np.zeros(B, np.int64)),
            eng.pools, i32(np.full((B, eng.max_pages), scratch, np.int64)),
            None)
        jax.block_until_ready(sampled)
        n_fused += 1
    stager = _need(eng, "stager")
    # the stager's gather (as SwapStager.pack issues it) and its scatter;
    # the slab stays on the device, since the host copy in between
    # compiles nothing
    ax = stager.page_axis
    for n in range(1, max_swap_pages(eng, warm) + 1):
        ids = [scratch] * n
        dev_ids = jnp.asarray(ids, jnp.int32)
        slab = jax.tree.map(lambda leaf: jnp.take(leaf, dev_ids, axis=ax),
                            eng.pools)
        eng.pools = stager.unpack(eng.pools, ids, slab)
        n_swap += 1
    jax.block_until_ready(eng.pools)
    return {"fused_buckets": n_fused, "swap_sizes": n_swap}
