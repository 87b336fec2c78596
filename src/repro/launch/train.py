"""End-to-end training driver with fault tolerance.

    PYTHONPATH=src python -m repro.launch.train --arch mamba2-130m \
        --steps 300 --batch 8 --seq 256 --ckpt-dir artifacts/train_run

Features wired in: auto-resume from the latest committed checkpoint, async
checkpoint writer, straggler monitor (per-host timings are simulated on this
single-host container but flow through the real code path), retry wrapper
around the step, deterministic resumable data.

On CPU the default is the real ~130M mamba2-130m config; --smoke uses the
reduced config for CI.
"""

from __future__ import annotations

import argparse
import json
import os
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mamba2-130m")
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--ckpt-dir", default="artifacts/train_run")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CI-sized)")
    ap.add_argument("--accum", type=int, default=1)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    from repro.ckpt import AsyncWriter, latest_step, restore
    from repro.configs import get_config, get_smoke_config
    from repro.data import SyntheticLM
    from repro.launch.mesh import make_local_mesh
    from repro.models import build_model
    from repro.runtime import StragglerMonitor, with_retries
    from repro.sharding import LogicalRules, ShardingCtx
    from repro.train import AdamW, make_train_step, warmup_cosine

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    model = build_model(cfg)
    mesh = make_local_mesh()
    sctx = ShardingCtx(mesh=mesh, rules=LogicalRules.default())
    opt = AdamW(lr=warmup_cosine(args.lr, args.warmup, args.steps),
                opt_dtype=jnp.bfloat16 if cfg.opt_dtype == "bfloat16"
                else jnp.float32)

    # ---- init or auto-resume --------------------------------------------
    start = latest_step(args.ckpt_dir)
    if start is not None:
        tree, start = restore(args.ckpt_dir)
        params, opt_state = tree["params"], tree["opt"]
        params = jax.tree_util.tree_map(jnp.asarray, params)
        opt_state = jax.tree_util.tree_map(jnp.asarray, opt_state)
        print(f"[train] resumed from step {start}")
        start += 1
    else:
        params = model.init(jax.random.PRNGKey(0))
        opt_state = opt.init(params)
        start = 0
        n = sum(x.size for x in jax.tree_util.tree_leaves(params))
        print(f"[train] fresh start: {cfg.name}, {n/1e6:.1f}M params")

    ds = SyntheticLM(vocab=cfg.vocab, seq_len=args.seq,
                     global_batch=args.batch, seed=0)
    step_fn = jax.jit(make_train_step(model, sctx, opt, accum=args.accum),
                      donate_argnums=(0, 1))
    step_fn = with_retries(step_fn, max_retries=2)

    writer = AsyncWriter()
    monitor = StragglerMonitor()
    t_hist = []
    log_path = os.path.join(args.ckpt_dir, "log.jsonl")
    os.makedirs(args.ckpt_dir, exist_ok=True)

    for step in range(start, args.steps):
        t0 = time.time()
        batch = ds.batch_at(step)
        params, opt_state, metrics = step_fn(params, opt_state, batch,
                                             jnp.int32(step))
        loss = float(metrics["loss"])
        dt = time.time() - t0
        t_hist.append(dt)
        flagged = monitor.update({0: dt})   # single-host: id 0
        if step % args.log_every == 0 or step == args.steps - 1:
            toks = args.batch * args.seq / dt
            print(f"[train] step {step:5d} loss {loss:.4f} "
                  f"{dt*1e3:7.1f} ms/step {toks:9.0f} tok/s"
                  + (f" STRAGGLERS {flagged}" if flagged else ""))
            with open(log_path, "a") as f:
                json.dump({"step": step, "loss": loss, "ms": dt * 1e3}, f)
                f.write("\n")
        if step > 0 and step % args.ckpt_every == 0:
            writer.submit(args.ckpt_dir, step,
                          {"params": params, "opt": opt_state})
    writer.submit(args.ckpt_dir, args.steps - 1,
                  {"params": params, "opt": opt_state})
    writer.flush()
    print(f"[train] done; final loss {loss:.4f}; "
          f"median step {sorted(t_hist)[len(t_hist)//2]*1e3:.1f} ms")


if __name__ == "__main__":
    main()
