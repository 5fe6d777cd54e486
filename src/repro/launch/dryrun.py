import os

if __name__ == "__main__":
    # 512 fake host devices for the production meshes; set before JAX
    # starts, and only when run as the dry-run script — importing this
    # module leaves the process's devices alone.
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run (deliverable e): lower + compile every
(architecture × input-shape × mesh) cell against the production meshes,
with ShapeDtypeStruct stand-ins (zero allocation), and record
memory_analysis / cost_analysis / collective traffic for §Roofline.

  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3.2-3b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --skip-existing
"""

import argparse  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs.registry import (  # noqa: E402
    ALL_IDS,
    ARCH_IDS,
    SHAPES,
    get_config,
    input_specs,
    shape_skips,
)
from repro.launch.hlo_analysis import collective_schedule, collective_stats  # noqa: E402
from repro.launch.hlo_cost import loop_aware_cost, top_collectives  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.roofline import Roofline, chip_peaks, model_flops  # noqa: E402
from repro.models.build import build  # noqa: E402
from repro.optim import adamw_init  # noqa: E402
from repro.sharding import batch_specs, cache_specs, param_rules  # noqa: E402
from repro.sharding.ctx import activation_sharding  # noqa: E402
from repro.train.loop import TrainState, make_train_step  # noqa: E402

# archs whose optimizer state must be bf16 to fit 512 v5e chips (noted in
# EXPERIMENTS.md §Dry-run)
_BF16_OPT = {"deepseek-v3-671b", "internvl2-76b", "mixtral-8x22b"}


def _named(mesh, spec_tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def build_cell(arch: str, shape: str, mesh, multi_pod: bool, overrides=None,
               bf16_params: bool = False):
    """Returns (jittable fn, arg SDS tuple, in_shardings tuple, meta)."""
    cfg = get_config(arch)
    if overrides:
        cfg = cfg.scaled(**overrides)
    model = build(cfg)
    info = SHAPES[shape]
    kind = info["kind"]
    seq, batch = info["seq"], info["batch"]
    rules = param_rules(cfg, multi_pod=multi_pod)
    pspecs = model.specs(rules)
    specs = input_specs(cfg, shape)
    bspecs = batch_specs(cfg, kind, multi_pod=multi_pod, batch=batch)

    if kind == "train":
        params_sds = model.abstract(jnp.float32)
        opt_dtype = jnp.bfloat16 if arch in _BF16_OPT else jnp.float32
        opt_sds = jax.eval_shape(lambda p: adamw_init(p, opt_dtype), params_sds)
        state_sds = TrainState(params_sds, opt_sds, None)
        opt_specs = {
            "mu": pspecs,
            "nu": pspecs,
            "step": P(),
        }
        state_specs = TrainState(pspecs, opt_specs, None)
        step = make_train_step(
            model.loss_fn,
            cast_params=jnp.bfloat16 if bf16_params else None,
        )
        args = (state_sds, specs)
        shardings = (_named(mesh, state_specs), _named(mesh, bspecs))
        return step, args, shardings, {"cfg": cfg, "model": model, "kind": kind,
                                       "seq": seq, "batch": batch}

    params_sds = model.abstract(jnp.bfloat16)  # serving weights
    cache_len = seq
    cache_dtype = jnp.bfloat16
    if model.init_cache_fn is None:  # encoder-style arch: no KV cache
        caches_sds, cspecs = None, None
    else:
        caches_sds = jax.eval_shape(
            lambda: model.init_cache_fn(batch, cache_len, cache_dtype)
        )
        cspecs = cache_specs(cfg, caches_sds, batch, multi_pod=multi_pod)

    if kind == "prefill":
        def step(params, batch_in, caches):
            return model.prefill_fn(params, batch_in, caches)

        args = (params_sds, specs, caches_sds)
        shardings = (_named(mesh, pspecs), _named(mesh, bspecs), _named(mesh, cspecs))
        return step, args, shardings, {"cfg": cfg, "model": model, "kind": kind,
                                       "seq": seq, "batch": batch}

    # decode
    def step(params, token, pos, caches):
        return model.decode_fn(params, token, pos, caches)

    args = (params_sds, specs["token"], specs["pos"], caches_sds)
    shardings = (
        _named(mesh, pspecs),
        _named(mesh, bspecs["token"]),
        _named(mesh, bspecs["pos"]),
        _named(mesh, cspecs),
    )
    return step, args, shardings, {"cfg": cfg, "model": model, "kind": kind,
                                   "seq": seq, "batch": batch}


def run_cell(arch: str, shape: str, multi_pod: bool, overrides=None,
             hlo_path: str | None = None, bf16_params: bool = False) -> dict:
    cfg = get_config(arch)
    skip = shape_skips(cfg, shape)
    mesh_name = "pod2_2x16x16" if multi_pod else "pod1_16x16"
    if skip:
        return {"arch": arch, "shape": shape, "mesh": mesh_name, "status": "skip",
                "reason": skip}
    from repro.sharding.rules import use_tp

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.size
    dp = ("pod", "data") if multi_pod else ("data",)
    dp_sizes = (2, 16) if multi_pod else (16,)
    cp = None
    if not use_tp(cfg):
        # pure 2-D batch FSDP: batch spreads over the model axis too; when
        # an INFERENCE batch can't fill it, attention falls back to context
        # parallelism over the same axis (ctx.cp_axis_for). Training keeps
        # plain 2-D batch: a global batch below mesh size is a configuration
        # smell at this scale, and CP-under-autodiff-under-remat explodes
        # host compile memory (documented in EXPERIMENTS.md §Dry-run).
        dp, dp_sizes = dp + ("model",), dp_sizes + (16,)
        tp = None
        info = SHAPES[shape]
        if info["kind"] != "train":
            cp = "model"
    else:
        tp = "model"
    t0 = time.perf_counter()
    step, args, shardings, meta = build_cell(
        arch, shape, mesh, multi_pod, overrides, bf16_params=bf16_params
    )
    with jax.set_mesh(mesh), activation_sharding(
        dp=dp, dp_sizes=dp_sizes, tp=tp, tp_size=16, cp=cp, cp_size=16,
    ):
        lowered = jax.jit(step, in_shardings=shardings).lower(*args)
        t_lower = time.perf_counter() - t0
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0 - t_lower
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    if hlo_path:
        with open(hlo_path, "w") as f:
            f.write(hlo)
    colls = collective_stats(hlo)          # static (once-per-body) breakdown
    lac = loop_aware_cost(hlo)             # loop-multiplied totals (§Roofline)
    sched = collective_schedule(hlo, limit=20)
    mf = model_flops(meta["cfg"], meta["model"].skeleton, meta["kind"],
                     meta["seq"], meta["batch"])
    rl = Roofline(
        flops_per_device=float(lac["flops"]),
        bytes_per_device=float(lac["bytes"]),
        collective_bytes_per_device=float(lac["collective_traffic_bytes"]),
        n_devices=n_dev,
        model_flops_global=mf,
        peaks=chip_peaks("TPU v5 lite"),  # the dry-run's target pod
    )
    return {
        "arch": arch,
        "shape": shape,
        "mesh": mesh_name,
        "status": "ok",
        "n_devices": n_dev,
        "kind": meta["kind"],
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "code_bytes": mem.generated_code_size_in_bytes,
            "total_bytes": mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes,
        },
        "cost_xla_once_per_body": {
            k: cost[k] for k in ("flops", "bytes accessed", "transcendentals")
            if k in cost
        },
        "cost": {"flops": lac["flops"], "bytes accessed": lac["bytes"]},
        "collectives": {k: v for k, v in colls.items() if isinstance(v, dict)},
        "collective_traffic_bytes": lac["collective_traffic_bytes"],
        "collective_count": lac["collective_count"],
        "schedule_head": sched,
        "top_collectives": top_collectives(hlo, 15),
        "roofline": rl.to_dict(),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", choices=["single", "multi", "both"], default="both")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--include-fourier", action="store_true",
                    help="also dry-run the paper's own fourier_lm arch")
    ap.add_argument("--moe-impl", default=None,
                    choices=["grouped_local", "ep_a2a", "dense_small"],
                    help="§Perf override: MoE dispatch path")
    ap.add_argument("--ep-axes", default="data,model",
                    help="mesh axes for expert parallelism (comma list)")
    ap.add_argument("--fft-variant", default=None,
                    choices=["looped", "unrolled", "stockham", "rfft"],
                    help="§Perf override: spectral mixing variant")
    ap.add_argument("--attn-block-q", type=int, default=None)
    ap.add_argument("--attn-block-k", type=int, default=None)
    ap.add_argument("--no-remat", action="store_true",
                    help="§Perf override: disable per-layer rematerialisation")
    ap.add_argument("--remat-policy", default=None, choices=["full", "dots"],
                    help="§Perf override: selective checkpoint policy")
    ap.add_argument("--save-hlo", action="store_true",
                    help="also dump the compiled HLO text next to the JSON")
    ap.add_argument("--bf16-params", action="store_true",
                    help="§Perf override: differentiate at a bf16 view of the "
                         "f32 master weights (bf16 gathers + grad reductions)")
    ap.add_argument("--tag", default="", help="suffix for the result file")
    args = ap.parse_args()

    archs = [args.arch] if args.arch else (
        ALL_IDS if args.include_fourier else ARCH_IDS
    )
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.multi_pod]
    os.makedirs(args.out, exist_ok=True)

    failures = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                overrides = {}
                if args.moe_impl:
                    import dataclasses

                    base_moe = get_config(arch).moe
                    if base_moe is not None:
                        overrides["moe"] = dataclasses.replace(
                            base_moe,
                            impl=args.moe_impl,
                            ep_axes=tuple(args.ep_axes.split(",")),
                        )
                if args.fft_variant:
                    overrides["fft_variant"] = args.fft_variant
                if args.no_remat:
                    overrides["remat"] = False
                if args.remat_policy:
                    overrides["remat_policy"] = args.remat_policy
                if args.attn_block_q:
                    overrides["attn_block_q"] = args.attn_block_q
                if args.attn_block_k:
                    overrides["attn_block_k"] = args.attn_block_k
                tag = f"{arch}__{shape}__{'multi' if mp else 'single'}"
                if args.tag:
                    tag += f"__{args.tag}"
                path = os.path.join(args.out, tag + ".json")
                if args.skip_existing and os.path.exists(path):
                    print(f"[skip-existing] {tag}")
                    continue
                print(f"[dryrun] {tag} ...", flush=True)
                try:
                    res = run_cell(
                        arch, shape, mp, overrides or None,
                        hlo_path=path.replace(".json", ".hlo.txt")
                        if args.save_hlo else None,
                        bf16_params=args.bf16_params,
                    )
                except Exception as e:  # record the failure, keep sweeping
                    res = {"arch": arch, "shape": shape,
                           "mesh": "multi" if mp else "single",
                           "status": "error", "error": f"{type(e).__name__}: {e}",
                           "trace": traceback.format_exc()[-3000:]}
                    failures.append(tag)
                with open(path, "w") as f:
                    json.dump(res, f, indent=1)
                if res["status"] == "ok":
                    r = res["roofline"]
                    print(
                        f"  ok: compute={r['compute_s']:.3e}s memory={r['memory_s']:.3e}s "
                        f"collective={r['collective_s']:.3e}s dominant={r['dominant']} "
                        f"(lower {res['lower_s']}s compile {res['compile_s']}s)",
                        flush=True,
                    )
                elif res["status"] == "skip":
                    print(f"  skip: {res['reason']}")
                else:
                    print(f"  ERROR: {res['error']}")
    if failures:
        print("FAILURES:", failures)
        raise SystemExit(1)
    print("dry-run sweep complete")


if __name__ == "__main__":
    main()
