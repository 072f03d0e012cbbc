"""Training driver.

    PYTHONPATH=src python -m repro.launch.train --arch stablelm-1.6b \
        --steps 200 --batch 8 --seq 256 --mesh 1x1 --ckpt /tmp/run1

Wires together: config registry -> model step (launch.steps semantics at
reduced scale) -> stateless data pipeline -> fault-tolerant train loop with
checkpoint/restart. ``--smoke`` uses the arch's reduced config so the whole
thing runs on CPU (the examples and integration tests drive this path).

``--heartbeat <sec>`` demonstrates the straggler/failure policy: the loop
touches a heartbeat file every step; the (external) supervisor relaunches
the rank when the file goes stale — restart resumes from ``latest`` with an
identical data stream (stateless pipeline), so a recomputed step is bitwise
the step the dead rank would have produced.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch
from repro.data import lm_tokens, recsys_batch
from repro.launch.mesh import batch_axes_of, make_mesh
from repro.models import recsys as rec_lib
from repro.models import transformer as tfm
from repro.optim import AdamWConfig, adamw_init, adamw_update
from repro.train import TrainLoopConfig, train_loop


def _parse():
    p = argparse.ArgumentParser()
    p.add_argument("--arch", required=True)
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=256)
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--mesh", default="1x1", help="DATAxMODEL, e.g. 2x4")
    p.add_argument("--ckpt", default=None)
    p.add_argument("--ckpt-every", type=int, default=50)
    p.add_argument("--smoke", action="store_true",
                   help="use the arch's reduced config (CPU-friendly)")
    p.add_argument("--heartbeat", default=None,
                   help="path to touch every step (supervisor watchdog)")
    p.add_argument("--seed", type=int, default=0)
    return p.parse_args()


def main():
    args = _parse()
    arch = get_arch(args.arch)
    cfg = arch.smoke_fn() if args.smoke else arch.config_fn()
    dshape = tuple(int(x) for x in args.mesh.split("x"))
    mesh = make_mesh(dshape, ("data", "model")) if np.prod(dshape) > 1 else None
    bA = ("data",) if mesh is not None else ()
    ocfg = AdamWConfig(lr=args.lr, total_steps=args.steps)
    key = jax.random.PRNGKey(args.seed)

    if arch.family == "lm":
        sh = tfm.ShardingConfig(batch_axes=bA or ("data",))
        params = tfm.init_params(cfg, key)
        loss_fn = lambda p, b: tfm.loss_fn(p, b, cfg, sh, mesh)
        make_batch = lambda s: jax.tree.map(
            jnp.asarray, lm_tokens(s, args.batch, args.seq, cfg.vocab,
                                   seed=args.seed))
    elif arch.family == "recsys":
        params = rec_lib.init_params(cfg, key)
        loss_fn = lambda p, b: rec_lib.loss_fn(p, b, cfg)
        make_batch = lambda s: jax.tree.map(
            jnp.asarray, recsys_batch(s, args.batch, cfg, seed=args.seed))
    else:
        raise SystemExit(f"launch.train drives lm/recsys archs; "
                         f"{args.arch} is {arch.family} — see examples/")

    opt_state = adamw_init(params)

    @jax.jit
    def step_fn(params, opt_state, batch):
        (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch)
        new_p, new_o, m = adamw_update(grads, opt_state, params, ocfg)
        return new_p, new_o, {"loss": loss, **m}

    hb = args.heartbeat

    def log_fn(step, msg):
        print(f"[train] {msg}", flush=True)

    def make_batch_hb(s):
        if hb:
            with open(hb, "w") as f:
                f.write(str(time.time()))
        return make_batch(s)

    tl_cfg = TrainLoopConfig(total_steps=args.steps, ckpt_dir=args.ckpt,
                             ckpt_every=args.ckpt_every)
    ctx = jax.set_mesh(mesh) if mesh is not None else _null()
    with ctx:
        params, opt_state, hist = train_loop(
            step_fn, params, opt_state, make_batch_hb, tl_cfg, log_fn=log_fn
        )
    if hist:
        print(f"[train] done: step {hist[-1][0]} loss {hist[-1][1]:.4f} "
              f"(first {hist[0][1]:.4f})")


class _null:
    def __enter__(self):
        return None

    def __exit__(self, *a):
        return False


if __name__ == "__main__":
    main()
