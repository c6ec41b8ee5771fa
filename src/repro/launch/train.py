"""Training launcher: end-to-end driver that trains a (reduced or full)
config on the synthetic pipeline with the production sharding rules.

On CPU (tests/examples) use --reduced with a small mesh; on a real pod the
same script runs with --mesh pod1/pod2.
"""
from __future__ import annotations

import argparse
import functools
import json
import time

import jax
import jax.numpy as jnp

from repro.checkpoint import save_checkpoint
from repro.data.pipeline import InputShape, SHAPES, make_batch
from repro.launch import sharding as SH
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_production_mesh, make_smoke_mesh
from repro.models import model as M
from repro.models import steps as ST
from repro.models.config import get_config, list_archs
from repro.optim import AdamWConfig


def train(arch: str, *, reduced: bool = True, steps: int = 20,
          batch: int = 8, seq: int = 256, microbatches: int = 1,
          mesh=None, log_every: int = 5, checkpoint_path: str | None = None,
          dtype=jnp.float32, seed: int = 0) -> dict:
    cfg = get_config(arch, reduced=reduced)
    shape = InputShape("custom_train", seq, batch, "train")
    mesh = mesh or make_smoke_mesh()
    policy = SH.ShardingPolicy.for_arch(cfg)
    opts = M.ModelOptions(remat=True)
    topts = ST.TrainOptions(microbatches=microbatches,
                            opt=AdamWConfig(),
                            schedule_total=max(steps, 2), schedule_warmup=max(steps // 10, 1))

    with mesh:
        state = ST.init_train_state(cfg, jax.random.PRNGKey(seed), dtype, topts)
        state_spec = SH.state_specs(state, mesh, policy)
        state_sh = SH.to_named(state_spec, mesh)
        batch_sh = SH.to_named(SH.batch_specs(cfg, shape, mesh), mesh)
        state = jax.device_put(state, state_sh)
        f = functools.partial(ST.train_step, cfg=cfg, opts=opts, topts=topts)
        step_fn = jax.jit(f, in_shardings=(state_sh, batch_sh),
                          out_shardings=(state_sh, None))

        history = []
        t0 = time.monotonic()
        for i in range(steps):
            b = make_batch(cfg, shape, seed=seed + i, dtype=dtype)
            state, metrics = step_fn(state, b)
            loss = float(metrics["loss"])
            history.append(loss)
            if i % log_every == 0 or i == steps - 1:
                print(f"step {i:5d}  loss {loss:.4f}  "
                      f"grad_norm {float(metrics['grad_norm']):.3f}")
        wall = time.monotonic() - t0

        if checkpoint_path:
            save_checkpoint(checkpoint_path, state,
                            meta={"arch": arch, "steps": steps,
                                  "final_loss": history[-1]})
    return {"arch": arch, "steps": steps, "first_loss": history[0],
            "final_loss": history[-1], "wall_s": round(wall, 1),
            "loss_history": history}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list_archs(), default="olmo-1b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--checkpoint", default=None)
    ap.add_argument("--mesh", choices=["smoke", "pod1", "pod2"], default="smoke")
    args = ap.parse_args()
    enable_compile_cache()
    mesh = (make_smoke_mesh() if args.mesh == "smoke"
            else make_production_mesh(multi_pod=args.mesh == "pod2"))
    rec = train(args.arch, reduced=args.reduced, steps=args.steps,
                batch=args.batch, seq=args.seq,
                microbatches=args.microbatches, mesh=mesh,
                checkpoint_path=args.checkpoint)
    print(json.dumps({k: v for k, v in rec.items() if k != "loss_history"},
                     indent=2))


if __name__ == "__main__":
    main()
