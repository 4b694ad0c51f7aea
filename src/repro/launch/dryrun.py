import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: prove the distribution config lowers + compiles for
the production mesh for every (architecture x input shape).

    PYTHONPATH=src python -m repro.launch.dryrun --arch deepseek-7b \
        --shape train_4k [--multi-pod] [--out experiments/dryrun]

The two lines above MUST stay the first statements in this module — jax
locks the device count at first backend init.
"""
import argparse
import json
import time
from typing import Optional

import jax

from ..analysis.roofline import (build_report, cost_analysis_dict,
                                 memory_analysis_dict)
from ..configs import ARCHS, SHAPES, get_arch, get_shape
from ..models.stack import Runtime
from ..optim import adamw
from ..sharding import (batch_shardings, cache_shardings, lora_shardings,
                        opt_state_shardings, params_shardings)
from .mesh import make_production_mesh
from .steps import (arch_for_shape, input_specs, make_decode_step,
                    make_prefill_step, make_train_step)


def default_runtime(shape_kind: str, mesh=None,
                    overrides: Optional[dict] = None) -> Runtime:
    dp = tuple(a for a in ("pod", "data") if mesh is not None
               and a in mesh.axis_names)
    rt = Runtime(attn_impl="chunked", kv_chunk=512, q_chunk=2048,
                 remat=(shape_kind == "train"),
                 dp_axes=dp, tp_axis="model" if mesh is not None else None)
    if overrides:
        rt = rt.replace(**overrides)
    return rt


def build_step_and_args(arch_name: str, shape_name: str, mesh,
                        rt_overrides: Optional[dict] = None,
                        lora_rank: Optional[int] = None,
                        full_finetune: bool = False):
    cfg = arch_for_shape(get_arch(arch_name), get_shape(shape_name))
    shape = get_shape(shape_name)
    rt = default_runtime(shape.kind, mesh, rt_overrides)
    opt = adamw(1e-4)
    args, _ = input_specs(cfg, shape, optimizer=opt, lora_rank=lora_rank)

    if shape.kind == "train" and full_finetune:
        # the baseline the paper's LoRA choice avoids: full fine-tuning
        from .steps import make_full_finetune_step
        from ..models import model as model_mod

        step = make_full_finetune_step(cfg, rt, opt)
        params = model_mod.abstract_params(cfg, args[0]["embed"]["tok"].dtype)
        opt_state = jax.eval_shape(opt.init, params)
        batch = args[3]
        p_sh = params_shardings(params, mesh)
        # m/v mirror the param shardings; step scalar replicated
        from jax.sharding import NamedSharding, PartitionSpec as P
        opt_sh = {"step": NamedSharding(mesh, P()), "m": p_sh, "v": p_sh}
        return cfg, shape, step, (params, opt_state, batch), (
            p_sh, opt_sh, batch_shardings(batch, mesh))

    if shape.kind == "train":
        step = make_train_step(cfg, rt, opt)
        params, lora, opt_state, batch = args
        shardings = (params_shardings(params, mesh),
                     lora_shardings(lora, mesh),
                     opt_state_shardings(opt_state, None, mesh),
                     batch_shardings(batch, mesh))
    elif shape.kind == "prefill":
        step = make_prefill_step(cfg, rt)
        params, lora, batch = args
        shardings = (params_shardings(params, mesh),
                     lora_shardings(lora, mesh),
                     batch_shardings(batch, mesh))
    else:
        step = make_decode_step(cfg, rt)
        params, lora, token, caches, cur = args
        shardings = (params_shardings(params, mesh),
                     lora_shardings(lora, mesh),
                     batch_shardings(token, mesh),
                     cache_shardings(caches, mesh),
                     batch_shardings(cur, mesh))
    return cfg, shape, step, args, shardings


def dryrun_one(arch_name: str, shape_name: str, *, multi_pod: bool = False,
               rt_overrides: Optional[dict] = None,
               lora_rank: Optional[int] = None,
               full_finetune: bool = False,
               verbose: bool = True) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    chips = mesh.size
    cfg, shape, step, args, shardings = build_step_and_args(
        arch_name, shape_name, mesh, rt_overrides, lora_rank,
        full_finetune=full_finetune)

    t0 = time.time()
    with jax.sharding.set_mesh(mesh):
        lowered = jax.jit(step, in_shardings=shardings).lower(*args)
        t1 = time.time()
        compiled = lowered.compile()
    t2 = time.time()

    mem = memory_analysis_dict(compiled)
    cost = cost_analysis_dict(compiled)
    hlo = compiled.as_text()
    report = build_report(arch=arch_name, shape_cfg=shape,
                          mesh_name=mesh_name, chips=chips,
                          compiled=compiled, lowered_text=hlo, cfg=cfg)
    result = {
        "arch": arch_name,
        "shape": shape_name,
        "mesh": mesh_name,
        "chips": chips,
        "lower_s": round(t1 - t0, 2),
        "compile_s": round(t2 - t1, 2),
        "memory_analysis": mem,
        "cost_analysis": {k: v for k, v in cost.items()
                          if isinstance(v, (int, float))},
        "collectives": report.coll_breakdown,
        "roofline": {
            "flops_per_device": report.flops,
            "bytes_per_device": report.bytes_accessed,
            "coll_bytes_per_device": report.coll_bytes,
            "t_compute": report.t_compute,
            "t_memory": report.t_memory,
            "t_collective": report.t_collective,
            "dominant": report.dominant,
            "model_flops_global": report.model_flops_global,
            "useful_ratio": report.useful_ratio,
        },
    }
    if verbose:
        print(f"== {arch_name} x {shape_name} @ {mesh_name} "
              f"(lower {result['lower_s']}s, compile {result['compile_s']}s)")
        print("memory_analysis:", json.dumps(mem))
        print("cost_analysis:", json.dumps(result["cost_analysis"]))
        rf = result["roofline"]
        print(f"roofline: compute {rf['t_compute']:.4g}s | memory "
              f"{rf['t_memory']:.4g}s | collective {rf['t_collective']:.4g}s "
              f"-> dominant: {rf['dominant']} | useful {rf['useful_ratio']:.3f}")
    return result


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="all",
                    help=f"one of {sorted(ARCHS)} or 'all'")
    ap.add_argument("--shape", default="all",
                    help=f"one of {sorted(SHAPES)} or 'all'")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun",
                    help="directory for per-pair JSON results")
    ap.add_argument("--rt", nargs="*", default=[],
                    help="Runtime overrides k=v (ints parsed)")
    ap.add_argument("--lora-rank", type=int, default=None)
    ap.add_argument("--full-ft", action="store_true",
                    help="full fine-tuning baseline (train shapes only)")
    args = ap.parse_args()

    overrides = {}
    for kv in args.rt:
        k, v = kv.split("=")
        try:
            overrides[k] = int(v)
        except ValueError:
            overrides[k] = v if v not in ("True", "False") else v == "True"

    from ..configs import ASSIGNED

    arch_names = ([a.name for a in ASSIGNED] if args.arch == "all"
                  else [args.arch])
    shape_names = sorted(SHAPES) if args.shape == "all" else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    os.makedirs(args.out, exist_ok=True)
    failures = []
    for arch in arch_names:
        for shape in shape_names:
            for mp in meshes:
                tag = f"{arch}_{shape}_{'2x16x16' if mp else '16x16'}"
                if args.full_ft:
                    tag += "_fullft"
                try:
                    res = dryrun_one(arch, shape, multi_pod=mp,
                                     rt_overrides=overrides,
                                     lora_rank=args.lora_rank,
                                     full_finetune=args.full_ft)
                    with open(os.path.join(args.out, tag + ".json"), "w") as f:
                        json.dump(res, f, indent=1)
                except Exception as e:  # noqa: BLE001 — report, keep sweeping
                    failures.append((tag, repr(e)))
                    print(f"!! FAILED {tag}: {e!r}")
    if failures:
        print(f"\n{len(failures)} failures:")
        for tag, err in failures:
            print(" ", tag, err[:200])
        raise SystemExit(1)
    print("\nall dry-runs passed")


if __name__ == "__main__":
    main()
