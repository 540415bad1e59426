"""Multi-pod dry-run: lower + compile every (arch × input shape × mesh).

MUST be run as its own process: the first two lines force 512 host
placeholder devices before jax initializes. Results (memory analysis, HLO
FLOPs/bytes, parsed collective bytes, roofline terms) are appended to a
JSONL cache so reruns skip completed combos.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]
"""
import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))

# ruff: noqa: E402
import argparse
import functools
import json
import pathlib
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import compile_cache, sharding as shd
from repro.config import INPUT_SHAPES, ModelConfig
from repro.configs import get_config, list_archs
from repro.launch import hlo_analysis as H
from repro.launch import specs as SP
from repro.launch.mesh import make_production_mesh, peaks
from repro.launch.steps import make_decode_step, make_prefill_step, make_train_step
from repro.models import model as mdl
from repro.train.optim import AdamWState

RESULTS = pathlib.Path(__file__).resolve().parents[3] / "experiments" / "dryrun.jsonl"

#: the chip the production mesh models: its roofline terms use this kind's
#: published peaks (the compile itself runs on placeholder CPU devices)
MODELED_DEVICE = "TPU v5 lite"

# Decode shapes are skipped for encoder-only archs; long_500k uses the
# sliding-window rolling cache for pure-attention archs (DESIGN.md §4).
PURE_ATTENTION = {"dense", "moe", "vlm"}


def combo_skip_reason(cfg: ModelConfig, shape_name: str):
    shape = INPUT_SHAPES[shape_name]
    if shape.kind == "decode" and not cfg.supports_decode:
        return "encoder-only: no decode step"
    return None


def _tree_size_bytes(tree):
    return sum(int(np.prod(x.shape)) * jnp.dtype(x.dtype).itemsize
               for x in jax.tree.leaves(tree))


def lower_combo(arch: str, shape_name: str, *, multi_pod: bool,
                moe_mode: str = "dense", q_chunk: int = 512,
                fsdp: bool = True, attn_layout: str = "grouped",
                kv_seq_axis: str | None = None, act_shard: bool = False,
                ssm_chunk: int | None = None):
    import dataclasses
    cfg = get_config(arch)
    if ssm_chunk and cfg.ssm is not None:  # §Perf lever: SSD chunk length
        cfg = dataclasses.replace(
            cfg, ssm=dataclasses.replace(cfg.ssm, chunk=ssm_chunk))
    shape = INPUT_SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = SP.activation_rules(mesh, shape, kv_seq_axis=kv_seq_axis,
                                act_shard=act_shard)

    params_shape = jax.eval_shape(
        functools.partial(mdl.init_params, cfg=cfg), jax.random.PRNGKey(0))
    pspecs = SP.param_specs(mesh, cfg, params_shape, fsdp=fsdp)
    rep = NamedSharding(mesh, P())

    rolling = (shape.name == "long_500k" and cfg.arch_type in PURE_ATTENTION)
    cache_len = cfg.sliding_window if rolling else shape.seq_len

    with mesh, shd.use_rules(mesh, rules):
        if shape.kind == "train":
            step, opt = make_train_step(cfg, moe_mode=moe_mode,
                                        q_chunk=q_chunk,
                                        attn_layout=attn_layout)
            opt_shape = jax.eval_shape(opt.init, params_shape)
            ospecs = AdamWState(step=rep, m=pspecs, v=pspecs)
            batch, bspecs = SP.input_specs(cfg, shape, mesh)
            jitted = jax.jit(step,
                             in_shardings=(pspecs, ospecs, bspecs),
                             out_shardings=(pspecs, ospecs, rep),
                             donate_argnums=(0, 1))
            lowered = jitted.lower(params_shape, opt_shape, batch)
            state_bytes = (_tree_size_bytes(params_shape)
                           + _tree_size_bytes(opt_shape))
        elif shape.kind == "prefill":
            step = make_prefill_step(cfg, moe_mode=moe_mode, q_chunk=q_chunk,
                                     attn_layout=attn_layout)
            batch, bspecs = SP.input_specs(cfg, shape, mesh)
            jitted = jax.jit(step, in_shardings=(pspecs, bspecs))
            lowered = jitted.lower(params_shape, batch)
            state_bytes = _tree_size_bytes(params_shape)
        else:  # decode
            step = make_decode_step(cfg, rolling=rolling, moe_mode=moe_mode)
            dshape = type(shape)(shape.name, cache_len, shape.global_batch,
                                 "decode")
            args, aspecs = SP.input_specs(cfg, dshape, mesh,
                                          kv_seq_axis=kv_seq_axis)
            jitted = jax.jit(
                step,
                in_shardings=(pspecs, aspecs["cache"], aspecs["tokens"],
                              aspecs["pos"]),
                out_shardings=(NamedSharding(
                    mesh, P(None if shape.global_batch == 1
                            else SP.batch_axes(mesh), None, None)),
                    aspecs["cache"]),
                donate_argnums=(1,))
            lowered = jitted.lower(params_shape, args["cache"],
                                   args["tokens"], args["pos"])
            state_bytes = (_tree_size_bytes(params_shape)
                           + _tree_size_bytes(args["cache"]))
    return cfg, shape, mesh, lowered, state_bytes, rolling


def run_combo(arch: str, shape_name: str, *, multi_pod: bool,
              moe_mode: str = "dense", q_chunk: int = 512,
              fsdp: bool = True, tag: str = "baseline", verbose: bool = True,
              attn_layout: str = "grouped", kv_seq_axis: str | None = None,
              act_shard: bool = False, ssm_chunk: int | None = None):
    t0 = time.time()
    cfg = get_config(arch)
    skip = combo_skip_reason(cfg, shape_name)
    n_chips = 512 if multi_pod else 256
    rec = {"arch": arch, "shape": shape_name,
           "mesh": "2x16x16" if multi_pod else "16x16",
           "moe_mode": moe_mode, "q_chunk": q_chunk, "fsdp": fsdp,
           "attn_layout": attn_layout, "kv_seq_axis": kv_seq_axis,
           "tag": tag}
    if skip:
        rec.update(status="skipped", reason=skip)
        return rec

    cfg, shape, mesh, lowered, state_bytes, rolling = lower_combo(
        arch, shape_name, multi_pod=multi_pod, moe_mode=moe_mode,
        q_chunk=q_chunk, fsdp=fsdp, attn_layout=attn_layout,
        kv_seq_axis=kv_seq_axis, act_shard=act_shard, ssm_chunk=ssm_chunk)
    t_lower = time.time() - t0
    compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    pk = peaks(MODELED_DEVICE)
    terms = H.roofline_terms(hlo, n_chips=n_chips,
                             peak_flops=pk["flops_bf16"], hbm_bw=pk["hbm_bw"],
                             ici_bw=pk["ici_bw"])

    params_shape = jax.eval_shape(
        functools.partial(mdl.init_params, cfg=cfg), jax.random.PRNGKey(0))
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params_shape))
    frac = (cfg.moe.top_k / cfg.moe.num_experts) if cfg.moe else 1.0
    n_active = sum(
        int(np.prod(x.shape) * (frac if len(x.shape) == 4 else 1.0))
        for x in jax.tree.leaves(params_shape))
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    flops_per_token = 6 if shape.kind == "train" else 2
    model_flops = flops_per_token * n_active * tokens

    mem_d = {}
    if mem is not None:
        for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                     "temp_size_in_bytes", "generated_code_size_in_bytes"):
            mem_d[attr] = getattr(mem, attr, None)

    hlo_flops_global = terms["hlo_flops_per_chip"] * n_chips
    rec.update(
        status="ok", rolling=rolling, modeled_device=MODELED_DEVICE,
        lower_s=round(t_lower, 1), compile_s=round(t_compile, 1),
        n_params=n_params, n_active=n_active,
        state_bytes_global=state_bytes,
        state_bytes_per_chip=state_bytes // n_chips,
        memory_analysis=mem_d,
        xla_cost_flops=cost.get("flops"),
        hlo_flops_per_chip=terms["hlo_flops_per_chip"],
        hlo_bytes_per_chip=terms["hlo_bytes_per_chip"],
        collective_bytes_per_chip=terms["collective_bytes_per_chip"],
        collectives=terms["collectives"],
        compute_s=terms["compute_s"], memory_s=terms["memory_s"],
        collective_s=terms["collective_s"], dominant=terms["dominant"],
        model_flops=model_flops,
        useful_flops_ratio=(model_flops / hlo_flops_global
                            if hlo_flops_global else None),
    )
    if verbose:
        print(f"[{arch} × {shape_name} × {rec['mesh']} × {tag}] "
              f"compile={t_compile:.0f}s dominant={rec['dominant']} "
              f"compute={rec['compute_s']*1e3:.2f}ms "
              f"memory={rec['memory_s']*1e3:.2f}ms "
              f"collective={rec['collective_s']*1e3:.2f}ms "
              f"useful={rec['useful_flops_ratio'] and round(rec['useful_flops_ratio'],3)}")
        print("  memory_analysis:", mem_d)
    return rec


def load_done(path=RESULTS):
    done = {}
    if path.exists():
        for line in path.read_text().splitlines():
            if line.strip():
                r = json.loads(line)
                done[(r["arch"], r["shape"], r["mesh"], r.get("tag",
                                                              "baseline"))] = r
    return done


def append(rec, path=RESULTS):
    path.parent.mkdir(parents=True, exist_ok=True)
    with open(path, "a") as f:
        f.write(json.dumps(rec) + "\n")


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--moe-mode", default="dense",
                    choices=["dense", "capacity"])
    ap.add_argument("--q-chunk", type=int, default=512)
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--attn-layout", default="grouped",
                    choices=["grouped", "flat"])
    ap.add_argument("--act-shard", action="store_true")
    ap.add_argument("--ssm-chunk", type=int, default=None)
    ap.add_argument("--kv-seq-axis", default=None,
                    choices=[None, "data", "model"])
    ap.add_argument("--tag", default="baseline")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    combos = []
    archs = [args.arch] if args.arch else list_archs()
    shapes = [args.shape] if args.shape else list(INPUT_SHAPES)
    for a in archs:
        for s in shapes:
            combos.append((a, s))

    done = load_done()
    mesh_name = "2x16x16" if args.multi_pod else "16x16"
    for a, s in combos:
        key = (a, s, mesh_name, args.tag)
        if not args.force and key in done and done[key]["status"] != "error":
            print(f"skip cached {key}")
            continue
        try:
            rec = run_combo(a, s, multi_pod=args.multi_pod,
                            moe_mode=args.moe_mode, q_chunk=args.q_chunk,
                            fsdp=not args.no_fsdp, tag=args.tag,
                            attn_layout=args.attn_layout,
                            kv_seq_axis=args.kv_seq_axis,
                            act_shard=args.act_shard,
                            ssm_chunk=args.ssm_chunk)
        except Exception as e:  # record failures — they are bugs to fix
            rec = {"arch": a, "shape": s, "mesh": mesh_name, "tag": args.tag,
                   "status": "error", "error": f"{type(e).__name__}: {e}"}
            print(f"[{a} × {s}] ERROR {rec['error']}")
        append(rec)


if __name__ == "__main__":
    main()
