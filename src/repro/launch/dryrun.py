import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the production mesh(es) with ShapeDtypeStruct stand-ins (no allocation), and
record memory/cost/roofline analysis.

    PYTHONPATH=src python -m repro.launch.dryrun --arch llama3.2-1b \
        --shape decode_32k --mesh single
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both

The two os.environ lines above MUST stay the first statements: jax locks the
device count on first init, and the dry-run needs 512 host placeholder
devices to build the 2x16x16 production mesh.
"""

import argparse      # noqa: E402
import json          # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

import jax           # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import ARCH_REGISTRY, INPUT_SHAPES, get_config  # noqa: E402
from repro.launch import roofline  # noqa: E402
from repro.launch.input_specs import (cache_struct, input_specs,  # noqa: E402
                                      params_struct, window_override_for)
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.sharding import (batch_sharding, cache_shardings,  # noqa: E402
                                   param_shardings)
from repro.launch.steps import (build_prefill_step, build_serve_step,  # noqa: E402
                                build_train_step)
from repro.training.optimizer import adamw_init  # noqa: E402
from repro.utils.hw import TPU_V5E  # noqa: E402


def choose_fsdp(cfg, mesh, mode: str) -> bool:
    """FSDP weight sharding: always for training; for serving only when
    model-parallel alone can't hold the weights in HBM."""
    if mode == "train":
        return True
    model_sz = mesh.shape.get("model", 1)
    per_chip = cfg.approx_n_params() * 2 / model_sz
    return per_chip > 0.6 * TPU_V5E.hbm_bytes


def _input_shardings(mesh, specs, global_batch):
    ax = batch_sharding(mesh, global_batch)

    def one(leaf):
        if leaf is None:
            return None
        spec = [None] * len(leaf.shape)
        if leaf.ndim >= 1 and ax is not None:
            spec[0] = ax
        return NamedSharding(mesh, P(*spec))
    return {k: (one(v) if v is not None else None) for k, v in specs.items()}


def lower_one(arch: str, shape_name: str, multi_pod: bool, *,
              compile_: bool = True, opts=()):
    """opts: hillclimb variants — subsets of
    {"seqpar", "no_tp", "expert2d"} (see EXPERIMENTS.md §Perf)."""
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.size
    wo = window_override_for(cfg, shape)
    fsdp = choose_fsdp(cfg, mesh, shape.mode)
    specs = input_specs(cfg, shape_name)
    params_s = params_struct(cfg)
    pshard = param_shardings(mesh, params_s, fsdp=fsdp,
                             tensor_parallel="no_tp" not in opts,
                             expert_2d="expert2d" in opts)

    if shape.mode == "train":
        _, fn = build_train_step(cfg)
        opt_s = jax.eval_shape(adamw_init, params_s)
        oshard = param_shardings(mesh, opt_s, fsdp=fsdp)
        ishard = _input_shardings(mesh, specs, shape.global_batch)
        jfn = jax.jit(
            fn,
            in_shardings=(pshard, oshard, ishard["tokens"], ishard["labels"],
                          ishard["mask"], ishard["embeds"]),
            out_shardings=(pshard, oshard, None))
        args = (params_s, opt_s, specs["tokens"], specs["labels"],
                specs["mask"], specs["embeds"])
        tokens_processed = shape.global_batch * shape.seq_len
    elif shape.mode == "prefill":
        _, fn = build_prefill_step(cfg, shape.seq_len, window_override=wo)
        cache_s = cache_struct(cfg, shape, wo)
        cshard = cache_shardings(mesh, cfg, cache_s)
        ishard = _input_shardings(mesh, specs, shape.global_batch)
        jfn = jax.jit(fn,
                      in_shardings=(pshard, ishard["tokens"],
                                    ishard["embeds"]),
                      out_shardings=(None, cshard))
        args = (params_s, specs["tokens"], specs["embeds"])
        tokens_processed = shape.global_batch * shape.seq_len
    else:  # decode
        seqpar = None
        if "seqpar" in opts:
            from repro.launch.sharding import batch_sharding as _bs
            seqpar = ("model", _bs(mesh, shape.global_batch))
        _, fn = build_serve_step(cfg, window_override=wo,
                                 seq_parallel=seqpar)
        cache_s = cache_struct(cfg, shape, wo)
        cshard = cache_shardings(mesh, cfg, cache_s)
        ishard = _input_shardings(mesh, specs, shape.global_batch)
        jfn = jax.jit(fn,
                      in_shardings=(pshard, cshard, ishard["tokens"],
                                    ishard["pos"]),
                      out_shardings=(None, None, cshard))
        args = (params_s, cache_s, specs["tokens"], specs["pos"])
        tokens_processed = shape.global_batch

    with jax.set_mesh(mesh):
        t0 = time.time()
        lowered = jfn.lower(*args)
        t_lower = time.time() - t0
        if not compile_:
            return {"arch": arch, "shape": shape_name,
                    "mesh": "multi" if multi_pod else "single",
                    "lower_s": round(t_lower, 1), "compiled": False}
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    mem = {}
    try:
        ma = compiled.memory_analysis()
        for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                     "temp_size_in_bytes", "generated_code_size_in_bytes",
                     "alias_size_in_bytes"):
            if hasattr(ma, attr):
                mem[attr] = int(getattr(ma, attr))
    except Exception as e:  # CPU backend may not support it
        mem["error"] = str(e)

    from repro.launch.mesh import axis_size, data_axes
    dshards = 1
    for a in data_axes(mesh):
        dshards *= axis_size(mesh, a)
    bshards = dshards if shape.global_batch % dshards == 0 else (
        axis_size(mesh, "data")
        if shape.global_batch % axis_size(mesh, "data") == 0 else 1)
    if "no_tp" in opts:
        mshards = 1
    else:
        mshards = axis_size(mesh, "model")
    rep = roofline.analyze(arch, shape_name,
                           "multi" if multi_pod else "single",
                           compiled=compiled, cfg=cfg, shape=shape,
                           chip=TPU_V5E, n_chips=n_chips,
                           tokens_processed=tokens_processed,
                           window_override=wo, model_shards=mshards,
                           data_shards=dshards, fsdp=fsdp,
                           batch_shards=bshards)
    out = rep.to_dict()
    out.update({"memory_analysis": mem, "lower_s": round(t_lower, 1),
                "compile_s": round(t_compile, 1), "fsdp": fsdp,
                "compiled": True})
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCH_REGISTRY), default=None)
    ap.add_argument("--shape", choices=sorted(INPUT_SHAPES), default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--opts", default="",
                    help="comma list: seqpar,no_tp,expert2d")
    args = ap.parse_args()
    opts = tuple(o for o in args.opts.split(",") if o)

    archs = sorted(ARCH_REGISTRY) if args.all or not args.arch \
        else [args.arch]
    shapes = sorted(INPUT_SHAPES) if args.all or not args.shape \
        else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    os.makedirs(args.out, exist_ok=True)
    failures = []
    for arch in archs:
        for shape in shapes:
            for multi in meshes:
                tag = f"{arch}__{shape}__{'multi' if multi else 'single'}"
                if opts:
                    tag += "__" + "_".join(opts)
                try:
                    res = lower_one(arch, shape, multi, opts=opts)
                    path = os.path.join(args.out, tag + ".json")
                    with open(path, "w") as f:
                        json.dump(res, f, indent=1)
                    print(f"OK   {tag:60s} compile={res['compile_s']:7.1f}s "
                          f"dominant={res.get('dominant', '?'):10s} "
                          f"flops/chip="
                          f"{res['flops_global']/res['n_chips']:.3e}",
                          flush=True)
                except Exception as e:
                    failures.append((tag, repr(e)))
                    traceback.print_exc()
                    print(f"FAIL {tag}: {e}", flush=True)
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for t, e in failures:
            print(" ", t, e)
        raise SystemExit(1)
    print("\nall dry-runs compiled")


if __name__ == "__main__":
    main()
