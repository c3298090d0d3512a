"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3_14b --reduced \
        --steps 200 --batch 8 --seq 128 --ckpt-dir /tmp/ck

On the CPU backend use ``--reduced`` (family-faithful small config).  On a
TPU pod slice the same entry point runs the full config: each host executes
this script (jax.distributed initializes from the TPU environment), the mesh
comes from ``make_production_mesh``, and per-host data sharding follows
process_index.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--token-file", default=None)
    ap.add_argument("--mesh", default=None,
                    help="e.g. '2x2' to shard across host devices")
    ap.add_argument("--d-model", type=int, default=None)
    ap.add_argument("--num-layers", type=int, default=None)
    args = ap.parse_args()

    import jax

    from repro.configs import get_config
    from repro.data import DataConfig, ShardedTokenPipeline
    from repro.launch.mesh import make_mesh
    from repro.models import ExecConfig, init_params, make_train_step
    from repro.optim import AdamWConfig
    from repro.optim.adamw import adamw_init
    from repro.runtime import Trainer, TrainerConfig

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    overrides = {}
    if args.d_model:
        overrides["d_model"] = args.d_model
    if args.num_layers:
        overrides["num_layers"] = args.num_layers
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)

    mesh = None
    exec_cfg = ExecConfig(attn_chunk_q=min(128, args.seq),
                          attn_chunk_k=min(256, args.seq),
                          ssm_chunk=min(64, args.seq),
                          loss_chunk=min(128, args.seq))
    if args.mesh:
        shape = tuple(int(x) for x in args.mesh.split("x"))
        mesh = make_mesh(shape, ("data", "model")[: len(shape)])
        exec_cfg = dataclasses.replace(exec_cfg, mesh=mesh)

    params = init_params(jax.random.PRNGKey(0), cfg)
    opt_cfg = AdamWConfig(lr=args.lr)
    opt = adamw_init(params, opt_cfg)
    step = jax.jit(make_train_step(cfg, opt_cfg, exec_cfg,
                                   total_steps=args.steps,
                                   warmup=max(1, args.steps // 20)),
                   donate_argnums=(0, 1))

    pipe = ShardedTokenPipeline(DataConfig(
        seq_len=args.seq, global_batch=args.batch, vocab=cfg.vocab,
        token_file=args.token_file,
        n_hosts=jax.process_count(), host_id=jax.process_index()))
    tc = TrainerConfig(total_steps=args.steps, ckpt_dir=args.ckpt_dir,
                       ckpt_every=args.ckpt_every)
    trainer = Trainer(tc, step, pipe, params, opt)
    t0 = time.time()
    out = trainer.run()
    dt = time.time() - t0
    tok_s = args.batch * args.seq * len(out["losses"]) / max(dt, 1e-9)
    print(json.dumps({
        "arch": cfg.name, "steps": out["step"],
        "final_loss": out["losses"][-1] if out["losses"] else None,
        "first_loss": out["losses"][0] if out["losses"] else None,
        "tokens_per_s": round(tok_s, 1),
        "restarts": out["restarts"],
    }))


if __name__ == "__main__":
    main()
