"""Distributed training driver with fault tolerance.

    PYTHONPATH=src python -m repro.launch.train --arch stablelm-3b-smoke \
        --steps 50 --batch 8 --seq 128 --mesh 1x1 --ckpt /tmp/run1

Integrates: config registry, sharded data pipeline, AdamW, checkpoint/
restart (atomic; exact-resume data state), straggler monitor, optional
gradient compression.  On this CPU container it runs reduced configs; the
same driver lowers the full configs on the production mesh (dry-run).
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from ..checkpoint.manager import CheckpointManager
from ..configs.base import ShapeSpec
from ..configs.registry import get
from ..data.pipeline import TokenStream, TokenStreamConfig
from ..dist import sharding as shd
from ..dist.steps import make_train_step, opt_config_for
from ..models.api import family_for
from ..optim import adamw
from ..runtime_ft.supervisor import StragglerMonitor


def build(cfg, mesh, *, seq: int, batch: int):
    shd.set_activation_mesh(mesh)
    fam = family_for(cfg)
    shape = ShapeSpec("train_cli", seq, batch, "train")
    p_specs = fam.param_specs(cfg)
    p_sh = shd.param_shardings(cfg, mesh, p_specs)
    opt_cfg = opt_config_for(cfg)
    o_specs = adamw.init_specs(opt_cfg, p_specs)
    o_sh = shd.opt_shardings(cfg, mesh, o_specs, p_sh)
    in_specs = fam.input_specs(cfg, shape)
    in_sh = shd.input_shardings(cfg, mesh, shape, in_specs)
    rep = shd.replicated(mesh)
    step = make_train_step(cfg, opt_cfg, microbatches=cfg.train_microbatches)
    jitted = jax.jit(
        step,
        in_shardings=(p_sh, o_sh, in_sh),
        out_shardings=(p_sh, o_sh, {"loss": rep, "grad_norm": rep}),
        donate_argnums=(0, 1),
    )
    return jitted, p_sh, o_sh, in_sh, opt_cfg, shape


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--mesh", type=str, default="1x1", help="DATAxMODEL")
    ap.add_argument("--ckpt", type=str, default=None)
    ap.add_argument("--save-every", type=int, default=25)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()

    cfg = get(args.arch)
    d, m = (int(x) for x in args.mesh.split("x"))
    mesh = shd.make_mesh((d, m), ("data", "model"))
    jitted, p_sh, o_sh, in_sh, opt_cfg, shape = build(
        cfg, mesh, seq=args.seq, batch=args.batch
    )
    fam = family_for(cfg)

    ckpt = CheckpointManager(args.ckpt) if args.ckpt else None
    stream = TokenStream(TokenStreamConfig(cfg.vocab, args.seq, args.batch))
    monitor = StragglerMonitor()

    start = 0
    params = jax.device_put(fam.init_params(cfg, jax.random.key(0)), p_sh)
    opt_state = jax.device_put(adamw.init(opt_cfg, params), o_sh)
    if ckpt and ckpt.latest_step() is not None:
        s = ckpt.latest_step()
        state = ckpt.restore(
            s, like={"params": params, "opt": opt_state, "data": 0}
        )
        params, opt_state = (
            jax.device_put(state["params"], p_sh),
            jax.device_put(state["opt"], o_sh),
        )
        stream.restore(state["data"])
        start = s
        print(f"[restore] step {s}")

    for step_i in range(start, args.steps):
        t0 = time.time()
        batch = stream.next_batch()
        if "tokens" in batch and cfg.family == "vlm":
            # vlm training consumes patches + shortened token seq
            B = batch["tokens"].shape[0]
            batch = {
                "patches": np.zeros(
                    (B, cfg.n_patches, cfg.d_model), np.float32
                ).astype(np.dtype("bfloat16") if hasattr(np, "bfloat16") else np.float32),
                "tokens": batch["tokens"][:, : args.seq - cfg.n_patches],
            }
        batch = jax.tree.map(
            lambda x, sh: jax.device_put(x, sh), batch, in_sh
        )
        params, opt_state, metrics = jitted(params, opt_state, batch)
        dt = time.time() - t0
        verdict = monitor.observe("host0", dt)
        if verdict != "ok":
            print(f"[straggler] host0 {verdict} ({dt:.2f}s)")
        if (step_i + 1) % args.log_every == 0:
            print(
                f"step {step_i+1}: loss {float(metrics['loss']):.4f} "
                f"gnorm {float(metrics['grad_norm']):.3f} ({dt:.2f}s)",
                flush=True,
            )
        if ckpt and (step_i + 1) % args.save_every == 0:
            ckpt.save(
                step_i + 1,
                {"params": params, "opt": opt_state, "data": stream.state()},
            )
    print("done")


if __name__ == "__main__":
    main()
