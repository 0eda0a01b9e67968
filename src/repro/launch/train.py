"""Production training launcher (the RDMA-NIC reference design analogue).

Single-process form of the per-host driver: builds the mesh (real devices
or the smoke mesh), shards params/optimizer per the policy (FSDP+ZeRO-1),
runs the fault-tolerant loop with checkpointing. On a real multi-pod TPU
job this same file runs under `jax.distributed.initialize()` on every host
with the production mesh from mesh.py.

  PYTHONPATH=src python -m repro.launch.train --arch qwen3-8b --smoke \
      --steps 20
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp

from repro.checkpoint import Checkpointer, latest_step
from repro.core.timing import Timer
from repro.configs.registry import ARCH_NAMES, get_config
from repro.data import DataConfig, SyntheticPackedDataset
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_production_mesh, make_smoke_mesh
from repro.models import lm
from repro.optim import AdamWConfig, adamw_init
from repro.sharding.policy import make_policy
from repro.train.train_step import make_train_step, train_shardings


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-8b", choices=list(ARCH_NAMES))
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config + 1-device mesh (CPU)")
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch, smoke=args.smoke)
    mesh = make_smoke_mesh() if args.smoke else make_production_mesh(
        multi_pod=args.multipod)
    policy = make_policy(mesh, multi_pod=args.multipod, sp=not args.smoke,
                         fsdp=not args.smoke)

    with mesh:
        params = lm.init_params(cfg, jax.random.PRNGKey(0),
                                tp=policy.tp_size)
        opt = adamw_init(params)
        (p_sh, o_sh, tok_sh), out_sh = train_shardings(cfg, policy)
        step = jax.jit(
            make_train_step(cfg, policy,
                            AdamWConfig(lr=args.lr, warmup_steps=10,
                                        total_steps=args.steps),
                            microbatch=args.microbatch),
            in_shardings=(p_sh, o_sh, tok_sh), out_shardings=out_sh,
            donate_argnums=(0, 1))

        data = SyntheticPackedDataset(DataConfig(
            seq_len=args.seq, global_batch=args.batch,
            vocab_size=cfg.vocab_size))
        ckpt = Checkpointer(args.ckpt_dir)
        start = 0
        if args.resume and latest_step(args.ckpt_dir) is not None:
            (params, opt), meta = ckpt.restore((params, opt))
            start = meta["step"]
            data.load_state_dict(meta["extra"].get("data", {"step": start}))
            print(f"resumed from step {start}")

        timer = Timer()
        for i in range(start, args.steps):
            toks, _ = data.next_batch()
            params, opt, metrics = step(params, opt, jnp.asarray(toks))
            if i % 5 == 0 or i == args.steps - 1:
                print(f"step {i:5d} loss={float(metrics['loss']):.4f} "
                      f"gnorm={float(metrics['grad_norm']):.3f} "
                      f"lr={float(metrics['lr']):.2e}")
            if (i + 1) % args.ckpt_every == 0:
                ckpt.save(i + 1, (params, opt),
                          extra={"data": data.state_dict()})
        ckpt.wait()
        dt = timer.elapsed()
        print(f"done: {args.steps - start} steps, "
              f"{(args.steps - start) * args.batch * args.seq / dt:.0f} tok/s")


if __name__ == "__main__":
    main()
