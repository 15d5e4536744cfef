"""LM training driver: mesh + sharded state + supervisor + checkpoints.

Runs real steps on whatever devices exist (``--mesh host``), or the
production mesh when launched on a pod. Example (CPU, reduced config):

    PYTHONPATH=src python -m repro.launch.train --arch qwen3_14b --reduced \
        --steps 20 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import logging
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint.manager import CheckpointManager
from repro.configs import get_config
from repro.data.pipeline import TokenPipeline, TokenPipelineConfig
from repro.distributed.sharding import shardings_for_tree, batch_spec
from repro.launch import steps as S
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models import init_model, unbox
from repro.models.layers import axes_tree
from repro.optim import adamw
from repro.runtime.fault import Supervisor, SupervisorConfig

log = logging.getLogger("repro.train")


def build_state(key, cfg, mesh, policy: str):
    boxed = init_model(key, cfg)
    params = unbox(boxed)
    p_axes = axes_tree(boxed)
    opt = adamw.init(params)
    state = S.TrainState(params, opt)
    shardings = S.TrainState(
        shardings_for_tree(p_axes, jax.eval_shape(lambda: params), mesh,
                           policy),
        adamw.AdamWState(
            step=NamedSharding(mesh, P()),
            m=shardings_for_tree(p_axes, jax.eval_shape(lambda: opt.m),
                                 mesh, policy),
            v=shardings_for_tree(p_axes, jax.eval_shape(lambda: opt.v),
                                 mesh, policy),
        ),
    )
    state = jax.device_put(state, shardings)
    return state, shardings


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--mesh", default="host", choices=["host", "single",
                                                       "multi"])
    ap.add_argument("--policy", default="fsdp_tp")
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args()
    from repro.runtime.compile_cache import use_compile_cache
    use_compile_cache()
    logging.basicConfig(level=logging.INFO)

    cfg = get_config(args.arch, reduced=args.reduced)
    if args.mesh == "host":
        mesh = make_host_mesh(args.model_parallel)
    else:
        mesh = make_production_mesh(multi_pod=args.mesh == "multi")

    pipe = TokenPipeline(TokenPipelineConfig(
        vocab_size=cfg.vocab_size, seq_len=args.seq,
        global_batch=args.batch))
    opt_cfg = adamw.AdamWConfig(lr=args.lr, total_steps=args.steps)
    train_step = S.make_train_step(cfg, opt_cfg)

    with mesh:
        state, shardings = build_state(jax.random.PRNGKey(0), cfg, mesh,
                                       args.policy)
        batch_sh = {
            k: NamedSharding(mesh, batch_spec(mesh, args.batch, v.ndim - 1))
            for k, v in pipe.batch(0).items()
        }
        jitted = jax.jit(
            train_step,
            in_shardings=(shardings, batch_sh),
            out_shardings=(shardings, None),
            donate_argnums=(0,),
        )

        ckpt = CheckpointManager(args.ckpt_dir)
        sup = Supervisor(ckpt, SupervisorConfig(
            checkpoint_every=args.ckpt_every))
        start = 0
        if args.resume and ckpt.latest_step() is not None:
            state, start = ckpt.restore(state, shardings=shardings)
            log.info("resumed from step %d", start)

        metrics_hist = []

        def step_fn(state, i):
            batch = jax.device_put(pipe.global_batch(i), batch_sh)
            state, metrics = jitted(state, batch)
            if (i + 1) % args.log_every == 0:
                m = {k: float(v) for k, v in metrics.items()}
                m["step"] = i + 1
                metrics_hist.append(m)
                log.info("step %d loss %.4f gnorm %.3f",
                         i + 1, m["loss"], m["grad_norm"])
            return state

        t0 = time.time()
        state = sup.run(state, step_fn, args.steps, start_step=start,
                        state_shardings=shardings)
        log.info("done: %d steps in %.1fs; restarts=%d stragglers=%d",
                 args.steps, time.time() - t0, sup.stats.restarts,
                 sup.stats.straggler_steps)
        if metrics_hist:
            log.info("first loss %.4f → last loss %.4f",
                     metrics_hist[0]["loss"], metrics_hist[-1]["loss"])


if __name__ == "__main__":
    main()
