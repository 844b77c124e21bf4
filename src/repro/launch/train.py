"""Production training driver: data pipeline -> pjit train step ->
checkpoint/restart -> straggler watchdog.

Single-host usage (CPU or one TPU VM):
  PYTHONPATH=src python -m repro.launch.train --arch qwen3_4b --smoke \\
      --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt

On a pod: run under the TPU launcher (one process per host); the data
pipeline shards by process, the mesh comes from make_production_mesh(),
and restarts resume from the latest atomic checkpoint — kill any host and
relaunch to see the fault-tolerance path.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import time

import jax

from repro import configs, dist
from repro.data.pipeline import SyntheticLM
from repro.dist import sharding as shd
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import lm
from repro.optim.adamw import AdamWConfig, adamw_init
from repro.train.checkpoint import (latest_step, restore_checkpoint,
                                    save_checkpoint)
from repro.train.loop import TrainConfig, make_train_step
from repro.train.watchdog import StragglerWatchdog


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="qwen3_4b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-2)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--wbits", type=int, nargs="+", default=[8])
    ap.add_argument("--abits", type=int, nargs="+", default=[8])
    ap.add_argument("--tp", type=int, default=1,
                    help="model-parallel ways of the host mesh")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = (configs.get_smoke(args.arch) if args.smoke
           else configs.get(args.arch))
    tcfg = TrainConfig(optimizer=AdamWConfig(lr=args.lr),
                       n_accum=args.accum,
                       wbits=tuple(args.wbits), abits=tuple(args.abits))

    # Mesh over whatever devices exist; with one device everything below
    # (constraints, placements, batch sharding) degrades to the identity.
    mesh = None
    if args.tp > 1 or len(jax.devices()) > 1:
        mesh = make_host_mesh(model=args.tp)
        print(f"[train] mesh {dict(mesh.shape)}")
    mesh_ctx = dist.use_mesh(mesh) if mesh is not None \
        else contextlib.nullcontext()

    with mesh_ctx:
        _run(args, cfg, tcfg, mesh)


def _run(args, cfg, tcfg, mesh) -> None:
    key = jax.random.PRNGKey(0)
    params = lm.init_params(cfg, key)
    opt = adamw_init(params, tcfg.optimizer)
    p_shd = o_shd = None
    if mesh is not None:
        p_shd = shd.param_shardings(params, mesh)
        o_shd = shd.opt_shardings(opt, mesh)
        params = jax.device_put(params, p_shd)
        opt = jax.device_put(opt, o_shd)
    step_fn, (wvec, avec) = make_train_step(tcfg, cfg, param_shardings=p_shd)
    step_fn = jax.jit(step_fn, donate_argnums=(0, 1))
    start = 0
    if args.ckpt_dir and latest_step(args.ckpt_dir) is not None:
        target = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
            {"params": params, "opt": opt})
        shardings = None
        if mesh is not None:
            shardings = {"params": p_shd, "opt": o_shd}
        restored, start = restore_checkpoint(args.ckpt_dir, target, shardings)
        params, opt = restored["params"], restored["opt"]
        print(f"[train] resumed from step {start}")

    data = SyntheticLM(seed=0, batch=args.batch, seq_len=args.seq + 1,
                       vocab=cfg.vocab_size, cfg=cfg, start_step=start)
    wd = StragglerWatchdog()
    t_start = time.time()
    step, metrics = start - 1, {"loss": float("nan")}
    for _ in range(args.steps):
        step, batch = next(data)
        wd.start()
        params, opt, metrics = step_fn(params, opt, batch)
        dt = wd.stop(step)
        if step % args.log_every == 0 or step == start:
            print(f"[train] step={step} loss={float(metrics['loss']):.4f} "
                  f"gnorm={float(metrics['grad_norm']):.3f} {dt:.2f}s")
        if args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
            save_checkpoint(args.ckpt_dir, step + 1,
                            {"params": params, "opt": opt})
            print(f"[train] checkpoint @ {step + 1}")
    data.close()
    if args.ckpt_dir:
        save_checkpoint(args.ckpt_dir, step + 1,
                        {"params": params, "opt": opt})
    print(f"[train] done: {args.steps} steps in {time.time() - t_start:.1f}s;"
          f" stragglers flagged: {len(wd.events)}")
    print(json.dumps({"final_loss": float(metrics["loss"]),
                      "steps": args.steps}))


if __name__ == "__main__":
    main()
