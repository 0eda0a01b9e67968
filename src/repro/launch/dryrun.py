import os
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=512"
    # keep per-layer bf16->f32 converts inside the scan loop: the CPU
    # backend otherwise hoists f32 copies of entire weight stacks
    # (LICM artifact; TPU keeps bf16 in HBM) — measured -11 GiB peak.
    + " --xla_disable_hlo_passes=while-loop-invariant-code-motion")
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware: sharding
mismatches, compile-time OOM, or unsupported collectives fail here. Records
memory_analysis / cost_analysis / the trip-count-aware HLO walk to JSON for
EXPERIMENTS.md §Dry-run and §Roofline.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multipod-only|--pod-only]
"""
import argparse
import json
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.configs.registry import CONFIGS, get_config
from repro.core.timing import Timer
from repro.launch.compile_cache import enable_compile_cache
from repro.configs.shapes import SHAPES_BY_NAME, applicable_shapes
from repro.launch.mesh import make_production_mesh
from repro.models import lm
from repro.optim import adamw_init
from repro.sharding.policy import make_policy
from repro.train.train_step import (make_decode_step, make_prefill_step,
                                    make_train_step, serve_shardings,
                                    train_shardings)

RESULTS_DIR = Path(__file__).resolve().parents[3] / "results" / "dryrun"

# Per-arch gradient-accumulation for the train shape: the 34B/52B models
# need microbatching to fit 16 GiB HBM chips at global_batch=256 x 4k
# (standard production choice; activations and CE buffers scale 1/mb).
TRAIN_MICROBATCH = {
    "jamba-v0.1-52b": 8,
    "chameleon-34b": 4,
    "nemotron-4-15b": 2,
}


def build_lowerable(arch: str, shape_name: str, multi_pod: bool,
                    policy_overrides=None):
    cfg = get_config(arch)
    shape = SHAPES_BY_NAME[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    long_ctx = shape.kind == "decode" and shape.global_batch < 16
    policy = make_policy(mesh, multi_pod=multi_pod,
                         sp=shape.kind in ("train", "prefill"),
                         shard_kv_seq=long_ctx,
                         fsdp=shape.kind == "train",
                         overrides=policy_overrides)
    tp = policy.tp_size
    specs = lm.input_specs(cfg, shape, tp=tp)

    if shape.kind == "train":
        step = make_train_step(cfg, policy,
                               microbatch=TRAIN_MICROBATCH.get(arch, 0))
        (p_sh, o_sh, tok_sh), out_sh = train_shardings(cfg, policy)
        params = lm.abstract_params(cfg, tp=tp)
        opt = jax.eval_shape(adamw_init, params)
        fn = jax.jit(step, in_shardings=(p_sh, o_sh, tok_sh),
                     out_shardings=out_sh, donate_argnums=(0, 1))
        args = (params, opt, specs["tokens"])
    elif shape.kind == "prefill":
        step = make_prefill_step(cfg, policy)
        p_sh = policy.tree_named(lm.param_specs(cfg))  # TP-stationary
        tok_sh = policy.named("batch", None)
        fn = jax.jit(step, in_shardings=(p_sh, tok_sh))
        args = (lm.abstract_params(cfg, tp=tp), specs["tokens"])
    else:  # decode
        step = make_decode_step(cfg, policy)
        (p_sh, tok_sh, st_sh), (lg_sh, st_out) = serve_shardings(cfg, policy)
        fn = jax.jit(step, in_shardings=(p_sh, tok_sh, st_sh),
                     out_shardings=(lg_sh, st_out), donate_argnums=(2,))
        args = (lm.abstract_params(cfg, tp=tp), specs["tokens"],
                specs["state"])
    return fn, args, mesh, cfg, shape


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             skip_analysis: bool = False, tag: str = "",
             policy_overrides=None) -> dict:
    from repro.launch import hlo_analysis
    timer = Timer()
    fn, args, mesh, cfg, shape = build_lowerable(
        arch, shape_name, multi_pod, policy_overrides)
    rec = {"arch": arch, "shape": shape_name,
           "mesh": "2x16x16" if multi_pod else "16x16",
           "kind": shape.kind, "tag": tag}
    with mesh:
        lowered = fn.lower(*args)
        t_lower = timer.lap()
        compiled = lowered.compile()
        t_compile = timer.lap()
        ma = compiled.memory_analysis()
        print(compiled.memory_analysis())
        ca = compiled.cost_analysis()
        print({k: ca.get(k) for k in ("flops", "bytes accessed")})
        rec.update(
            lower_s=round(t_lower, 2), compile_s=round(t_compile, 2),
            memory={
                "argument_bytes": ma.argument_size_in_bytes,
                "output_bytes": ma.output_size_in_bytes,
                "temp_bytes": ma.temp_size_in_bytes,
                "alias_bytes": ma.alias_size_in_bytes,
                "peak_estimate_bytes": ma.argument_size_in_bytes
                    + ma.temp_size_in_bytes + ma.output_size_in_bytes
                    - ma.alias_size_in_bytes,
            },
            xla_cost={"flops": ca.get("flops", -1.0),
                      "bytes_accessed": ca.get("bytes accessed", -1.0)})
        if not skip_analysis:
            txt = compiled.as_text()
            rec["hlo_chars"] = len(txt)
            parsed = hlo_analysis.analyze(txt)
            rec["parsed"] = parsed
    rec["n_devices"] = len(jax.devices())
    return rec


def cell_list(multi_pod_filter=None):
    cells = []
    for arch in CONFIGS:
        for shape in applicable_shapes(arch):
            for mp in (False, True):
                if multi_pod_filter is not None and mp != multi_pod_filter:
                    continue
                cells.append((arch, shape.name, mp))
    return cells


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--pod-only", action="store_true")
    ap.add_argument("--multipod-only", action="store_true")
    ap.add_argument("--skip-analysis", action="store_true",
                    help="lower+compile only (multi-pod pass/fail sweep)")
    ap.add_argument("--tag", default="baseline")
    ap.add_argument("--out", default=str(RESULTS_DIR))
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()

    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)

    if args.all:
        mp_filter = False if args.pod_only else (
            True if args.multipod_only else None)
        cells = cell_list(mp_filter)
    else:
        assert args.arch and args.shape
        cells = [(args.arch, args.shape, args.multipod)]

    failures = []
    for arch, shape, mp in cells:
        mesh_tag = "2x16x16" if mp else "16x16"
        fname = out_dir / f"{args.tag}__{arch}__{shape}__{mesh_tag}.json"
        if fname.exists() and not args.force:
            print(f"[skip cached] {fname.name}")
            continue
        print(f"=== {arch} x {shape} x {mesh_tag} ===", flush=True)
        try:
            rec = run_cell(arch, shape, mp,
                           skip_analysis=args.skip_analysis, tag=args.tag)
            fname.write_text(json.dumps(rec, indent=1))
            peak = rec["memory"]["peak_estimate_bytes"] / 2**30
            print(f"  ok: compile={rec['compile_s']}s peak={peak:.2f}GiB",
                  flush=True)
        except Exception as e:
            traceback.print_exc()
            failures.append((arch, shape, mesh_tag, repr(e)))
    if failures:
        print("\nFAILURES:")
        for f in failures:
            print(" ", f)
        raise SystemExit(1)
    print("\nall cells passed")


if __name__ == "__main__":
    main()
