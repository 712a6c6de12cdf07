import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: prove every (architecture x input shape x mesh)
combination lowers, compiles, and fits -- without hardware.

For each cell it lowers the real train/prefill/decode step with abstract
params/batch under the production mesh, compiles, and records:
  * memory_analysis()    -- per-device argument/output/temp bytes,
  * cost_analysis()      -- HLO FLOPs / bytes for the roofline,
  * collective bytes     -- parsed from the compiled HLO text,
  * analytic per-device shard bytes (params / optimizer / cache / batch).

Usage:
  python -m repro.launch.dryrun --arch gemma2-2b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--out DIR]
"""
import argparse          # noqa: E402
import json              # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402
from pathlib import Path  # noqa: E402

import jax               # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import SHAPES, get_config, valid_cells        # noqa: E402
from repro.launch.mesh import make_production_mesh               # noqa: E402
from repro.launch.sharding import (batch_pspecs, cache_pspecs,    # noqa: E402
                                   hidden_batch_axes, make_plan,
                                   param_pspecs, to_named)
from repro.launch.steps import (AdamWConfig, make_decode_step,    # noqa: E402
                                make_prefill_step, make_train_step)
from repro.models.model import build_model                        # noqa: E402
from repro.models.transformer import set_mesh_axes                # noqa: E402
from repro.utils.costmodel import cell_cost                       # noqa: E402
from repro.utils.hlo import parse_collectives                     # noqa: E402

# v5e constants (roofline terms; see DESIGN.md §8)
PEAK_FLOPS = 197e12
HBM_BW = 819e9
ICI_BW = 50e9
HBM_BYTES = 16 * (1 << 30)


def shard_bytes(tree, shardings) -> int:
    """Analytic per-device bytes of a (abstract) array tree."""
    total = 0
    for leaf, sh in zip(jax.tree.leaves(tree), jax.tree.leaves(
            shardings, is_leaf=lambda x: hasattr(x, "shard_shape"))):
        shp = sh.shard_shape(leaf.shape) if hasattr(sh, "shard_shape") \
            else leaf.shape
        n = 1
        for d in shp:
            n *= d
        total += n * jnp.dtype(leaf.dtype).itemsize
    return total


def model_flops(cfg, cell) -> float:
    """MODEL_FLOPS: 6*N*D train, 2*N*D per generated/processed token."""
    n = cfg.active_param_count()
    if cell.mode == "train":
        tokens = cell.seq_len * cell.global_batch
        return 6.0 * n * tokens
    if cell.mode == "prefill":
        tokens = cell.seq_len * cell.global_batch
        return 2.0 * n * tokens
    return 2.0 * n * cell.global_batch          # decode: one token/seq


def run_cell(arch: str, shape: str, multi_pod: bool = False,
             plan_override=None, remat: str = "full",
             cfg_override=None, seq_shard: bool = False,
             cp: bool = False) -> dict:
    cfg = get_config(arch)
    cell = SHAPES[shape]
    cfg = cfg.replace(max_seq=cell.seq_len)
    if cfg_override:
        cfg = cfg.replace(**cfg_override)
    mesh = make_production_mesh(multi_pod=multi_pod)
    model = build_model(cfg)
    chips = mesh.size
    plan = plan_override or make_plan(cfg, mesh)

    set_mesh_axes(hidden_batch_axes(plan, mesh, cell.global_batch), "model",
                  mesh=mesh, seq_shard=seq_shard and plan.kind == "tp",
                  cp=cp)
    t0 = time.time()
    with mesh:
        pspecs = param_pspecs(model, mesh, plan)
        pshard = to_named(mesh, pspecs)
        bspec = model.batch_spec(cell.seq_len, cell.global_batch, cell.mode)
        bshard = to_named(mesh, batch_pspecs(model, mesh, bspec,
                                             cell.global_batch, plan))
        arg_bytes = {}

        if cell.mode == "train":
            params = model.abstract_params("float32")
            from repro.optim.adamw import init_opt_state
            opt = jax.eval_shape(init_opt_state, params)
            opt_shard = {"m": pshard, "v": pshard,
                         "step": to_named(mesh, jax.sharding.PartitionSpec())}
            step = make_train_step(model, AdamWConfig(), remat=remat)
            jitted = jax.jit(step,
                             in_shardings=(pshard, opt_shard, bshard),
                             donate_argnums=(0, 1))
            lowered = jitted.lower(params, opt, bspec)
            arg_bytes = {
                "params": shard_bytes(params, pshard),
                "opt": shard_bytes(opt["m"], pshard) * 2,
                "batch": shard_bytes(bspec, bshard),
            }
        elif cell.mode == "prefill":
            params = model.abstract_params("bfloat16")
            cache = model.abstract_cache(cell.global_batch, cell.seq_len)
            cshard = to_named(mesh, cache_pspecs(cache, mesh,
                                                 cell.global_batch, plan))
            step = make_prefill_step(model)
            jitted = jax.jit(step, in_shardings=(pshard, bshard, cshard),
                             donate_argnums=(2,))
            lowered = jitted.lower(params, bspec, cache)
            arg_bytes = {
                "params": shard_bytes(params, pshard),
                "cache": shard_bytes(cache, cshard),
                "batch": shard_bytes(bspec, bshard),
            }
        else:                                    # decode
            params = model.abstract_params("bfloat16")
            cache = model.abstract_cache(cell.global_batch, cell.seq_len)
            cshard = to_named(mesh, cache_pspecs(cache, mesh,
                                                 cell.global_batch, plan))
            step = make_decode_step(model)
            jitted = jax.jit(
                step,
                in_shardings=(pshard, cshard, bshard["tokens"]),
                donate_argnums=(1,))
            lowered = jitted.lower(params, cache, bspec["tokens"])
            arg_bytes = {
                "params": shard_bytes(params, pshard),
                "cache": shard_bytes(cache, cshard),
                "batch": shard_bytes(bspec, bshard),
            }

        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        coll = parse_collectives(compiled.as_text())

    flops = float((cost or {}).get("flops", 0.0))
    bytes_acc = float((cost or {}).get("bytes accessed", 0.0))
    mflops = model_flops(cfg, cell)
    # Analytic accounting (utils/costmodel.py): cost_analysis() counts
    # while bodies once, so the roofline terms come from the exact einsum
    # model; raw HLO numbers are reported alongside.
    ac = cell_cost(cfg, cell, chips, remat=remat)
    # traffic attribution: params divide by the number of distinct param
    # shards (model x data-FSDP), activations/KV by the batch shards.
    model_n = mesh.shape.get("model", 1)
    data_n = mesh.shape.get("data", 1) * mesh.shape.get("pod", 1)
    param_shards = model_n * (data_n if (plan.fsdp and plan.kind == "tp")
                              else 1)
    bspec_used = plan.batch_spec(mesh, cell.global_batch)
    batch_shards = 1
    if len(bspec_used):
        ax0 = tuple(bspec_used)[0]
        for a in (ax0 if isinstance(ax0, tuple) else (ax0,)):
            batch_shards *= mesh.shape[a]
    bytes_dev = ac.weight_bytes / param_shards + ac.act_bytes / batch_shards
    # FLOPs spread over the chips that actually compute.  TP plan: the
    # model axis participates everywhere.  Hybrid plan: the ff-TP MLP
    # spreads over all chips, the head-replicated attention only over the
    # batch shards (or all chips with CP prefill attention).
    if plan.kind == "tp":
        flops_dev = ac.flops / chips
    else:
        from repro.utils.costmodel import attention_fraction
        S_eff = 1 if cell.mode == "decode" else cell.seq_len
        af = attention_fraction(cfg, S_eff,
                                cell.seq_len if cell.mode == "decode"
                                else (cell.seq_len + 1) / 2, cell.mode)
        attn_shards = chips if (cp and cell.mode == "prefill") \
            else batch_shards
        flops_dev = ac.flops * (af / attn_shards + (1 - af) / chips)
    compute_s = flops_dev / PEAK_FLOPS
    memory_s = bytes_dev / HBM_BW
    collective_s = coll.total_bytes / ICI_BW
    step_s = max(compute_s, memory_s, collective_s)
    dominant = max(
        [("compute", compute_s), ("memory", memory_s),
         ("collective", collective_s)], key=lambda kv: kv[1])[0]

    mem_d = {}
    if mem is not None:
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes"):
            try:
                mem_d[k] = int(getattr(mem, k))
            except Exception:
                pass

    result = {
        "arch": arch, "shape": shape,
        "mesh": list(mesh.devices.shape), "axes": list(mesh.axis_names),
        "chips": chips, "mode": cell.mode,
        "plan": plan.kind, "fsdp": plan.fsdp, "seq_shard": seq_shard, "cp": cp,
        "remat": remat,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "hlo_flops_per_device": flops,
        "hlo_bytes_per_device": bytes_acc,
        "collectives": coll.summary(),
        "memory_analysis": mem_d,
        "arg_bytes_per_device": arg_bytes,
        "total_arg_bytes_per_device": sum(arg_bytes.values()),
        "fits_hbm": sum(arg_bytes.values()) < HBM_BYTES,
        "model_flops_global": mflops,
        "model_flops_per_device": mflops / chips,
        "analytic_flops_per_device": flops_dev,
        "analytic_bytes_per_device": bytes_dev,
        "roofline": {
            "compute_s": compute_s,
            "memory_s": memory_s,
            "collective_s": collective_s,
            "step_s": step_s,
            "dominant": dominant,
            # MODEL_FLOPS / analytic HLO-equivalent flops: how much of the
            # compiled compute is "useful" (remat/dispatch overhead shows
            # up here)
            "useful_flops_frac": mflops / ac.flops if ac.flops else None,
            "mfu_bound": (mflops / chips / step_s) / PEAK_FLOPS
            if step_s else None,
        },
        "ok": True,
    }
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--remat", default="full")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)

    cells = valid_cells() if args.all else [(args.arch, args.shape)]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    failures = 0
    for arch, shape in cells:
        for mp in meshes:
            tag = f"{arch}_{shape}_{'pod2' if mp else 'pod1'}"
            try:
                res = run_cell(arch, shape, multi_pod=mp,
                               remat=args.remat)
                r = res["roofline"]
                print(f"[OK] {tag}: dominant={r['dominant']} "
                      f"compute={r['compute_s']:.4f}s "
                      f"memory={r['memory_s']:.4f}s "
                      f"collective={r['collective_s']:.4f}s "
                      f"args={res['total_arg_bytes_per_device'] / 2**30:.2f}"
                      f"GiB/dev fits={res['fits_hbm']}")
            except Exception as e:
                failures += 1
                res = {"arch": arch, "shape": shape, "ok": False,
                       "multi_pod": mp, "error": repr(e),
                       "trace": traceback.format_exc()[-2000:]}
                print(f"[FAIL] {tag}: {e!r}")
            (out_dir / f"{tag}.json").write_text(json.dumps(res, indent=1))
    if failures:
        raise SystemExit(f"{failures} cells failed")
    print("all requested cells compiled.")


if __name__ == "__main__":
    main()
