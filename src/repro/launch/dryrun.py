"""Multi-pod dry-run: lower + compile every (arch x input-shape) combination
on the production mesh, print memory/cost analysis, and record the roofline
inputs. No real arrays are ever allocated (ShapeDtypeStruct in, AOT out).

Usage:
  python -m repro.launch.dryrun --arch yi-9b --shape train_4k --mesh pod1
  python -m repro.launch.dryrun --all --mesh pod1 --out experiments/dryrun

``main()`` gives the CPU backend 512 virtual devices (``XLA_FLAGS``) before
the first device use; importing this module changes no process state.
"""
import argparse
import functools
import json
import os
import time
import traceback

import jax
import jax.numpy as jnp

from repro.data.pipeline import SHAPES, InputShape, input_specs
from repro.launch import sharding as SH
from repro.launch.hlo_analysis import summarize_compiled
from repro.launch.mesh import make_production_mesh
from repro.models import model as M
from repro.models import steps as ST
from repro.models.config import ArchConfig, get_config, list_archs
from repro.optim import AdamWConfig

# gradient-accumulation factor for train_4k (keeps per-microbatch activation
# memory inside a v5e's HBM; recorded per-arch in EXPERIMENTS.md)
MICROBATCHES = {
    "olmo-1b": 1, "internvl2-1b": 1, "mamba2-2.7b": 2, "hubert-xlarge": 1,
    "yi-9b": 4, "recurrentgemma-9b": 4, "nemotron-4-15b": 4,
    "qwen3-moe-30b-a3b": 4, "moonshot-v1-16b-a3b": 2, "grok-1-314b": 16,
}

LONG_WINDOW = 4096  # sliding-window size for long_500k on quadratic archs


def applicability(cfg: ArchConfig, shape: InputShape) -> str | None:
    """Return a skip reason or None if the combo runs (see DESIGN.md)."""
    if shape.kind == "decode" and cfg.is_encoder:
        return "encoder-only architecture: no decode step"
    return None


def model_options(cfg: ArchConfig, shape: InputShape,
                  ring_cache: bool = False, remat: bool = True,
                  moe_local: bool = False,
                  blockwise_attention: int = 0,
                  gqa_expand_kv: bool = False,
                  moe_expert_constraint: bool = False) -> M.ModelOptions:
    window = 0
    if shape.name == "long_500k" and cfg.attention_is_quadratic:
        window = LONG_WINDOW      # sub-quadratic variant (attn=sliding)
    return M.ModelOptions(use_kernels=False, window_override=window,
                          ring_cache=ring_cache,
                          remat=remat and shape.kind == "train",
                          moe_local_dispatch=moe_local,
                          blockwise_attention=blockwise_attention,
                          gqa_expand_kv=gqa_expand_kv and shape.kind == "train",
                          moe_expert_shard_constraint=moe_expert_constraint)


def build_lowered(cfg: ArchConfig, shape: InputShape, mesh,
                  moe_shard_map: bool = False,
                  policy: SH.ShardingPolicy | None = None,
                  ring_cache: bool = False,
                  microbatches: int | None = None,
                  moe_local: bool = False,
                  blockwise_attention: int = 0,
                  gqa_expand_kv: bool = False,
                  moe_expert_constraint: bool = False,
                  dtype=jnp.bfloat16):
    """Construct the jitted step for this combo and .lower() it (no compile)."""
    policy = policy or SH.ShardingPolicy.for_arch(cfg)
    opts = model_options(cfg, shape, ring_cache=ring_cache,
                         moe_local=moe_local,
                         blockwise_attention=blockwise_attention,
                         gqa_expand_kv=gqa_expand_kv,
                         moe_expert_constraint=moe_expert_constraint)
    if moe_shard_map:
        import dataclasses as _dc
        dp = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
        opts = _dc.replace(opts, moe_shard_map_mesh=mesh, moe_shard_map_dp=dp)
    key = jax.random.PRNGKey(0)

    batch_sds = input_specs(cfg, shape, dtype=dtype)
    batch_spec = SH.batch_specs(cfg, shape, mesh)
    batch_sh = SH.to_named(batch_spec, mesh)

    if shape.kind == "train":
        mb = microbatches if microbatches is not None else MICROBATCHES.get(cfg.name, 1)
        opt_dtype = jnp.bfloat16 if cfg.param_count() > 1e11 else jnp.float32
        dp_axes = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
        topts = ST.TrainOptions(microbatches=mb,
                                opt=AdamWConfig(state_dtype=opt_dtype),
                                batch_axes=dp_axes if mb > 1 else ())
        state_sds = jax.eval_shape(
            lambda: ST.init_train_state(cfg, key, dtype, topts))
        state_spec = SH.state_specs(state_sds, mesh, policy)
        state_sh = SH.to_named(state_spec, mesh)
        f = functools.partial(ST.train_step, cfg=cfg, opts=opts, topts=topts)
        jitted = jax.jit(f, in_shardings=(state_sh, batch_sh),
                         out_shardings=(state_sh, None))
        return jitted.lower(state_sds, batch_sds), {"microbatches": mb}

    params_sds = jax.eval_shape(lambda: M.init_params(cfg, key, dtype))
    params_spec = SH.params_specs(params_sds, mesh, policy)
    params_sh = SH.to_named(params_spec, mesh)
    dp = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    dpn = 1
    for a in dp:
        dpn *= mesh.shape[a]
    batch_ax = dp if (shape.global_batch > 1 and
                      shape.global_batch % dpn == 0) else None
    vocab_ax = "model" if cfg.vocab_size % mesh.shape["model"] == 0 else None
    logits_sh = SH.to_named(
        jax.sharding.PartitionSpec(batch_ax, vocab_ax), mesh)

    if shape.kind == "prefill":
        f = functools.partial(ST.prefill_step, cfg=cfg, opts=opts,
                              cache_len=shape.seq_len)
        cache_sds = jax.eval_shape(
            lambda: M.init_cache(cfg, shape.global_batch, shape.seq_len,
                                 dtype, opts))
        cache_spec = SH.cache_specs(cache_sds, cfg, shape, mesh, policy)
        cache_sh = SH.to_named(cache_spec, mesh)
        jitted = jax.jit(f, in_shardings=(params_sh, batch_sh),
                         out_shardings=(logits_sh, cache_sh))
        return jitted.lower(params_sds, batch_sds), {}

    # decode
    cache_sds = jax.eval_shape(
        lambda: M.init_cache(cfg, shape.global_batch, shape.seq_len, dtype,
                             opts))
    cache_spec = SH.cache_specs(cache_sds, cfg, shape, mesh, policy)
    cache_sh = SH.to_named(cache_spec, mesh)
    f = functools.partial(ST.decode_step, cfg=cfg, opts=opts)
    jitted = jax.jit(f, in_shardings=(params_sh, cache_sh, batch_sh),
                     out_shardings=(logits_sh, cache_sh))
    return jitted.lower(params_sds, cache_sds, batch_sds), {}


def run_one(arch: str, shape_name: str, mesh_name: str,
            ring_cache: bool = False, microbatches: int | None = None,
            policy: SH.ShardingPolicy | None = None,
            legacy_expert_sharding: bool = False,
            decode_seq_over_model: bool = False,
            moe_local: bool = False,
            blockwise_attention: int = 0,
            gqa_expand_kv: bool = False,
            moe_expert_constraint: bool = False,
            moe_shard_map: bool = False,
            fsdp_off: bool = False,
            hlo_dir: str | None = None,
            tag: str = "") -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    multi_pod = mesh_name == "pod2"
    if policy is None:
        base = SH.ShardingPolicy.for_arch(cfg)
        import dataclasses as _dc
        policy = _dc.replace(
            base,
            fsdp=base.fsdp and not fsdp_off,
            expert_fallback_shard=not legacy_expert_sharding,
            decode_seq_over_model=decode_seq_over_model)
    rec: dict = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "params": cfg.param_count(), "active_params": cfg.active_param_count(),
        "ring_cache": ring_cache,
        "moe_local": moe_local,
        "blockwise_attention": blockwise_attention,
        "policy": {"fsdp": policy.fsdp,
                   "expert_fallback_shard": policy.expert_fallback_shard,
                   "decode_seq_over_model": policy.decode_seq_over_model},
    }
    reason = applicability(cfg, shape)
    if reason:
        rec["skipped"] = reason
        return rec
    if shape.name == "long_500k" and cfg.attention_is_quadratic:
        rec["attn"] = "sliding"
    t0 = time.monotonic()
    mesh = make_production_mesh(multi_pod=multi_pod)
    with mesh:
        lowered, extra = build_lowered(cfg, shape, mesh, policy=policy,
                                       moe_shard_map=moe_shard_map,
                                       ring_cache=ring_cache,
                                       microbatches=microbatches,
                                       moe_local=moe_local,
                                       blockwise_attention=blockwise_attention,
                                       gqa_expand_kv=gqa_expand_kv,
                                       moe_expert_constraint=moe_expert_constraint)
        rec.update(extra)
        rec["lower_s"] = round(time.monotonic() - t0, 1)
        t1 = time.monotonic()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.monotonic() - t1, 1)
        if hlo_dir:
            import gzip
            os.makedirs(hlo_dir, exist_ok=True)
            hp = os.path.join(hlo_dir,
                              f"{tag}{arch}_{shape_name}_{mesh_name}.hlo.gz")
            with gzip.open(hp, "wt") as hf:
                hf.write(compiled.as_text())
            rec["hlo_path"] = hp
        rec.update(summarize_compiled(lowered, compiled))
        print(f"--- {arch} x {shape_name} x {mesh_name} ---")
        print(compiled.memory_analysis())
        ca = compiled.cost_analysis()
        if isinstance(ca, list):
            ca = ca[0]
        print({k: ca[k] for k in ("flops", "bytes accessed") if k in ca})
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list_archs())
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=["pod1", "pod2"], default="pod1")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--ring-cache", action="store_true")
    ap.add_argument("--microbatches", type=int, default=None)
    ap.add_argument("--legacy-expert-sharding", action="store_true",
                    help="pre-iteration-1 baseline behaviour (experts "
                         "replicate when E %% model_axis != 0)")
    ap.add_argument("--decode-seq-over-model", action="store_true",
                    help="perf iteration 3: shard KV-cache seq over model")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

    combos = ([(a, s) for a in list_archs() for s in SHAPES]
              if args.all else [(args.arch, args.shape)])
    os.makedirs(args.out, exist_ok=True)
    n_ok = n_skip = n_fail = 0
    for arch, shape in combos:
        tag = "ring_" if args.ring_cache else ""
        path = os.path.join(args.out, f"{tag}{arch}_{shape}_{args.mesh}.json")
        if args.skip_existing and os.path.exists(path):
            continue
        try:
            rec = run_one(arch, shape, args.mesh, ring_cache=args.ring_cache,
                          microbatches=args.microbatches,
                          legacy_expert_sharding=args.legacy_expert_sharding,
                          decode_seq_over_model=args.decode_seq_over_model,
                          hlo_dir=os.path.join(args.out, "hlo"), tag=tag)
            if "skipped" in rec:
                n_skip += 1
            else:
                n_ok += 1
        except Exception as e:
            rec = {"arch": arch, "shape": shape, "mesh": args.mesh,
                   "error": f"{type(e).__name__}: {e}",
                   "traceback": traceback.format_exc()}
            n_fail += 1
            print(f"FAIL {arch} x {shape} x {args.mesh}: {e}")
        with open(path, "w") as f:
            json.dump(rec, f, indent=2)
    print(f"dry-run done: {n_ok} ok, {n_skip} skipped, {n_fail} failed")
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
