#!/usr/bin/env python3
"""Bring-up smoke test: serve Llama-3.2-1B at full width on one TPU.

    python chip_smoke.py [--seed N]

One process, in this order:

1. device check — the first JAX device must be a TPU whose ``device_kind``
   is in ``repro.utils.hw.DEVICE_KINDS``; anything else exits non-zero
   before any other work (there is no CPU fallback);
2. JAX's persistent compilation cache (``JAX_COMPILATION_CACHE_DIR`` if
   set, else ``<repo>/.jax_cache``);
3. the served Pallas kernels (``ragged_paged_attention``, ``kv_append``)
   against their jnp references at full width;
4. the served fused step (``LM.forward_mixed_paged``, Pallas) against the
   plain contiguous ``LM.extend_step`` on one 512-token prompt;
5. the engine through the session API (``repro.launch.serve.serve``):
   scripted intercept sessions plus one live tool session, InferCept
   policy, bf16 weights and KV, a KV pool pinched against the workload so
   that the policy swaps and discards;
6. a second engine, built after the first is freed, whose pool holds
   4 GiB of KV — the size a deployment of this model would keep on one
   chip — serving a short burst; prints the device memory in use.

Weights are random, made from ``--seed``. Every phase raises on a failed
check, so the script exits 0 only when all of them passed; its last line
is then one JSON object naming the device. The numbers printed on the
way (seconds, tokens) are bring-up facts, not benchmark results.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core import POLICIES  # noqa: E402
from repro.core.request import SamplingParams  # noqa: E402
from repro.kernels import ref  # noqa: E402
from repro.kernels.kv_append import kv_append  # noqa: E402
from repro.kernels.ragged_paged_attention import \
    ragged_paged_attention  # noqa: E402
from repro.launch.serve import scale_to_budget, serve  # noqa: E402
from repro.serving.engine import Engine  # noqa: E402
from repro.serving.session import InterceptEvent  # noqa: E402
from repro.serving.workloads import make_workload  # noqa: E402
from repro.utils.compile_cache import enable_compile_cache  # noqa: E402
from repro.utils.hw import chip_of  # noqa: E402

# ragged attention: bf16 q/K/V of unit scale give outputs of O(1); the
# kernel and the reference differ by bf16 rounding of the output and of
# the probabilities fed to the MXU — a few 1e-3 each
KERNEL_TOL = 0.05
# served (Pallas) vs plain (jnp) last-position logits, max |delta| over
# the plain logits' spread: 16 bf16 layers that round in different
# orders, measured in the low 1e-2 range
MODEL_TOL = 0.05
PAGE = 16
MAX_MODEL_LEN = 4096
# the pool is pinched against the workload, not sized to the chip: 16
# sessions with prompts drawn from 512..2048 hold ~20k tokens of context
# (~0.66 GB of KV) — 8192 slots make the policy swap, evict and discard
N_PAGES = 512              # 8192 token slots, ~0.27 GB of bf16 KV
N_SESSIONS = 16
BURST_RPS = 1000.0         # sessions arrive together: their contexts
                           # contend for the pinched pool at once
PROMPT_RANGE = (512, 2048)
# the deployment-sized pool: 16 layers x K,V x 16 slots x 8 heads x 64 x
# 2 B = 512 KiB a page, so 8192 pages hold 4 GiB beside ~2.5 GB of weights
BIG_N_PAGES = 8192         # 131072 token slots
BIG_SESSIONS = 8


class SmokeFailure(AssertionError):
    pass


def check(ok: bool, what: str):
    if not ok:
        raise SmokeFailure(what)


def check_kernels(seed: int, *, Hkv=8, G=4, hd=64, page=PAGE,
                  max_pages=64, interpret=False):
    """Phase 3: the two served kernels against their references, on
    layer 1 of pools stacked over two layers (the model's layout)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    B, N, L, layer = 4, 64, 2, 1
    n_pages = B * max_pages + 1                     # page 0: discard page
    bf = jnp.bfloat16
    shape = (L, n_pages, page, Hkv, hd)
    k_pool = jax.random.normal(ks[0], shape).astype(bf)
    v_pool = jax.random.normal(ks[1], shape).astype(bf)
    q = jax.random.normal(ks[2], (N, Hkv, G, hd)).astype(bf)
    bt = 1 + np.random.default_rng(seed).permutation(
        B * max_pages).reshape(B, max_pages)
    ctx_max = max_pages * page
    # a decode deep in its context, two prefill chunks, a short decode,
    # and padded rows (tok_pos -1) filling the bucket
    seq, pos = [0], [ctx_max - 1]
    seq += [1] * 30
    pos += list(range(200, 230))
    seq += [2] * 20
    pos += list(range(ctx_max // 2, ctx_max // 2 + 20))
    seq += [3]
    pos += [17]
    real = len(seq)
    seq += [0] * (N - real)
    pos += [-1] * (N - real)
    bt, ts, tp = (jnp.asarray(a, jnp.int32) for a in (bt, seq, pos))
    got = ragged_paged_attention(q, k_pool, v_pool, layer, bt, ts, tp,
                                 interpret=interpret)
    want = ref.ragged_paged_attention_ref(q, k_pool, v_pool, layer, bt, ts,
                                          tp)
    err = float(jnp.max(jnp.abs(got[:real].astype(jnp.float32)
                                - want[:real].astype(jnp.float32))))
    print(f"kernel ragged_paged_attention: max|delta|={err:.6g} "
          f"(tol {KERNEL_TOL}) over {real} tokens, Hkv={Hkv} G={G} "
          f"hd={hd} page={page}")
    check(err <= KERNEL_TOL, f"ragged_paged_attention error {err}")

    # kv_append: distinct live slots; invalid rows go to the discard page
    rng = np.random.default_rng(seed + 1)
    slots = rng.choice((n_pages - 1) * page, N, replace=False) + page
    valid = (rng.random(N) < 0.8).astype(np.int32)
    pids = np.where(valid, slots // page, 0).astype(np.int32)
    offs = (slots % page).astype(np.int32)
    k_new = jax.random.normal(ks[3], (N, Hkv, hd)).astype(bf)
    v_new = jax.random.normal(ks[4], (N, Hkv, hd)).astype(bf)
    args = (layer, k_new, v_new, jnp.asarray(pids), jnp.asarray(offs),
            jnp.asarray(valid))
    want_k, want_v = ref.kv_append_ref(k_pool, v_pool, *args)
    # kv_append donates its pools: give it copies, keep the originals
    got_k, got_v = kv_append(k_pool.copy(), v_pool.copy(), *args,
                             interpret=interpret)
    # every page but layer 1's discard page, layer 0 whole
    err = max(float(jnp.max(jnp.abs(g[i, lo:].astype(jnp.float32)
                                    - w[i, lo:].astype(jnp.float32))))
              for g, w in ((got_k, want_k), (got_v, want_v))
              for i, lo in ((0, 0), (layer, 1)))
    print(f"kernel kv_append: max|delta|={err:.6g} (tol 0, exact) over "
          f"{int(valid.sum())} live rows")
    check(err == 0.0, f"kv_append differs from its reference by {err}")


def check_model(eng: Engine, seed: int, prompt_len: int = 512):
    """Phase 4: the fused served step against the plain contiguous path
    on one prompt, at the engine's weights."""
    m, params = eng.model, eng.params
    rng = np.random.default_rng(seed + 2)
    toks = jnp.asarray(rng.integers(0, eng.cfg.vocab_size, prompt_len),
                       jnp.int32)
    n_pg = -(-prompt_len // eng.page)
    pools = m.init_cache(n_pg + 1, eng.page, dtype=jnp.bfloat16)
    bt = jnp.arange(1, n_pg + 1, dtype=jnp.int32)[None]   # page 0: discard
    served = jax.jit(lambda p, t, pools, bt: m.forward_mixed_paged(
        p, t, jnp.zeros_like(t), jnp.arange(t.shape[0], dtype=jnp.int32),
        jnp.asarray([t.shape[0] - 1], jnp.int32), pools, bt,
        discard_pid=0)[1])
    cache = m.init_cache(1, prompt_len, dtype=jnp.bfloat16)
    plain = jax.jit(lambda p, t, c: m.extend_step(
        p, t[None], jnp.zeros((1,), jnp.int32), c)[0])
    got = served(params, toks, pools, bt).astype(jnp.float32)
    want = plain(params, toks, cache).astype(jnp.float32)
    spread = float(jnp.max(want) - jnp.min(want))
    rel = float(jnp.max(jnp.abs(got - want))) / spread
    agree = bool(jnp.argmax(got) == jnp.argmax(want))
    print(f"model forward_mixed_paged vs extend_step: max|delta|/spread="
          f"{rel:.6g} (tol {MODEL_TOL}), spread={spread:.4g}, "
          f"argmax agree={agree}, prompt={prompt_len} tokens")
    check(bool(np.isfinite(rel)) and rel <= MODEL_TOL,
          f"served logits differ from the plain path by {rel} of spread")


def smoke_workload(seed: int, n: int, max_len: int, prompt_range):
    """Scripted Table-1 intercept sessions (at least one interception
    each) with prompt lengths drawn uniformly from ``prompt_range``."""
    reqs = make_workload(seed=seed, n_requests=n, rate_rps=BURST_RPS,
                         max_ctx=max_len)
    lo, hi = prompt_range
    rng = np.random.default_rng(seed + 3)
    for r in reqs:
        r.prompt_len = int(rng.integers(lo, hi + 1))
    return scale_to_budget(reqs, max_len, prompt_cap=hi)


def check_serve(eng: Engine, seed: int, *, n_sessions=N_SESSIONS,
                prompt_range=PROMPT_RANGE, pinched=True):
    """Phases 5 and 6: the engine through the session API. Only a pinched
    pool must make the policy swap out and back in."""
    reqs = smoke_workload(seed, n_sessions, eng.max_pages * eng.page,
                          prompt_range)
    res = serve(eng, reqs, SamplingParams(),
                live_prompt_len=prompt_range[0])
    states = [h.state for h in res.sessions]
    intercepts = sum(isinstance(e, InterceptEvent) for e in res.events)
    st = eng.sched.stats
    generated = sum(h.request.output_tokens for h in res.sessions)
    prompts = [r.prompt_len for r in reqs]
    print(f"serve: sessions={len(states)} finished={states.count('finished')}"
          f" prompts={min(prompts)}..{max(prompts)} tokens, "
          f"generated_tokens={generated} intercepts={intercepts} "
          f"wall_s={res.wall_s:.3f}")
    print(f"serve: scheduler decode_tokens={st.decode_tokens} "
          f"fresh={st.fresh_tokens} recompute={st.recompute_tokens} "
          f"swapped_out={st.swapped_out_tokens} "
          f"swapped_in={st.swapped_in_tokens} preserves={st.preserves} "
          f"discards={st.discards} evictions={st.evictions} "
          f"dispatches={eng.counters['device_dispatches']} "
          f"pool_pages={eng.blocks.n_pages}")
    check(all(s == "finished" for s in states),
          f"sessions not all finished: {states}")
    check(intercepts > 0, "no interception fired")
    if pinched:
        check(st.swapped_out_tokens > 0, "nothing was swapped out")
        check(st.swapped_in_tokens > 0, "nothing was swapped in")


def tree_bytes(tree) -> int:
    return sum(int(leaf.nbytes) for leaf in jax.tree.leaves(tree))


def memory_line(dev, what: str) -> dict:
    """Print the device's memory counters (``Device.memory_stats``)."""
    ms = dev.memory_stats() or {}
    print(f"memory {what}: " + (" ".join(
        f"{k}={ms[k]}" for k in ("bytes_in_use", "peak_bytes_in_use",
                                 "bytes_limit") if k in ms)
        or "not reported"))
    return ms


def check_big_pool(eng: Engine, dev, seed: int):
    """Phase 6: serve a burst on an engine with a deployment-sized pool;
    the pool and the weights must be resident on the device."""
    weights, pool = tree_bytes(eng.params), tree_bytes(eng.pools)
    jax.block_until_ready((eng.params, eng.pools))
    print(f"big pool: pages={eng.blocks.n_pages} pool_bytes={pool} "
          f"weight_bytes={weights}")
    ms = memory_line(dev, "after engine init")
    if "bytes_in_use" in ms:
        check(ms["bytes_in_use"] >= pool + weights,
              f"{ms['bytes_in_use']} B in use, below pool + weights")
    check_serve(eng, seed, n_sessions=BIG_SESSIONS, pinched=False)
    memory_line(dev, "after serve")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform} "
              f"({dev.device_kind}); refusing to run", file=sys.stderr)
        return 2
    chip = chip_of(dev)                     # unknown device_kind raises
    cache_dir, compiles = enable_compile_cache()
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(jax.devices())} peaks={chip.name}")
    print(f"compile cache: {cache_dir}")

    def phase(name, fn, *a, **kw):
        c0, h0, m0 = compiles.compile_s, compiles.hits, compiles.misses
        t0 = time.perf_counter()
        out = fn(*a, **kw)
        print(f"phase {name}: wall_s={time.perf_counter() - t0:.3f} "
              f"compile_s={compiles.compile_s - c0:.3f} "
              f"cache_hits={compiles.hits - h0} "
              f"cache_misses={compiles.misses - m0}")
        return out

    phase("kernels", check_kernels, args.seed)
    cfg = get_config("llama3.2-1b")
    eng = phase("engine_init", Engine, cfg, POLICIES["infercept"],
                page_size=PAGE, n_pages=N_PAGES,
                max_model_len=MAX_MODEL_LEN, seed=args.seed,
                dtype=jnp.bfloat16)
    phase("model", check_model, eng, args.seed)
    phase("serve", check_serve, eng, args.seed)
    eng.close()
    del eng                 # free its weights and pool before the next one
    gc.collect()
    memory_line(dev, "after the pinched engine is freed")
    big = phase("big_pool_init", Engine, cfg, POLICIES["infercept"],
                page_size=PAGE, n_pages=BIG_N_PAGES,
                max_model_len=MAX_MODEL_LEN, seed=args.seed,
                dtype=jnp.bfloat16)
    phase("big_pool_serve", check_big_pool, big, dev, args.seed)
    big.close()
    print(f"compile total: compile_s={compiles.compile_s:.3f} "
          f"cache_hits={compiles.hits} cache_misses={compiles.misses}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
