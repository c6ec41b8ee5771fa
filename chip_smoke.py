"""On-chip smoke test: the served path at full width on one TPU.

Run from the root of a checkout, on a machine with one TPU:

    python3 chip_smoke.py

Everything runs in this one process (a chip belongs to one process at a
time). The phases, each of which raises on failure:

  0. device     JAX must report a TPU. There is no CPU fallback: on any other
                platform the script prints why and exits 1.
  1. kernels    each Pallas kernel compiled for the chip at the widths of the
                architecture that uses it, against its ``kernels/ref.py``
                oracle in f32 at highest matmul precision.
  2. serving    ``repro.launch.serve.serve("olmo-1b", reduced=False)``: 24
                frame requests (4 streams x 2 fps x 3 s) of 8 new tokens on
                bf16 weights, then the three fleet plans.
  3. reference  a ``ContinuousBatchingEngine`` built as ``serve`` builds it
                (same weights and options, 8 slots) serves ten seeded
                requests, one of 200 tokens, with slots retired and
                re-admitted mid-decode. Each request's logits, read off the
                engine's program calls, are compared with an f32 ``jnp``
                forward of the same weights over the prompt plus the tokens
                the engine generated: first-token logits (the prefill) and
                every decode step (the slot KV cache). The compiled prefill
                must contain the Pallas kernel (``tpu_custom_call``).

The last line of standard output is ``{"ok": true, "device": {...}}``; it is
printed only when every phase passed. The phase functions take ``reduced``
so that tests can rehearse their control flow on the CPU at the reduced
configs.
"""
from __future__ import annotations

import functools
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.kernels import ref  # noqa: E402
from repro.kernels.flash_attention import flash_attention  # noqa: E402
from repro.kernels.rglru_scan import rglru_scan  # noqa: E402
from repro.kernels.ssd_scan import ssd_scan  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.serve import init_weights, serve  # noqa: E402
from repro.models import layers  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.models.config import get_config  # noqa: E402
from repro.serving import ContinuousBatchingEngine, Request  # noqa: E402

ARCH = "olmo-1b"
STREAMS, FPS, SECONDS = 4, 2.0, 3          # serve()'s defaults: 24 frames
NEW_TOKENS, PROMPT_LEN = 8, 32             # per frame request, as in serve()
SLOTS = 8                                  # the continuous engine's pool
PLANS = {"per-stream", "uniform-big", "packed"}

# Phase 3's requests, (prompt length, new tokens) in submission order, with
# prompts drawn from REF_SEED. Ten requests for eight slots: the first
# retires after 2 tokens and its slot takes the 200-token prompt (longer
# than one 128-row kernel block, not a multiple of it) mid-decode; the last
# waits for the next slot to free. REF_CACHE_LEN holds the longest request.
REF_REQUESTS = (((PROMPT_LEN, 2),) + ((PROMPT_LEN, NEW_TOKENS),) * 7
                + ((200, NEW_TOKENS), (PROMPT_LEN, NEW_TOKENS)))
REF_CACHE_LEN = 256
REF_SEED = 0

# max |kernel - oracle| / max |oracle|. The MXU kernels may run their f32
# matmuls as bf16 passes (~2^-8 relative); the RG-LRU scan is f32 VPU work.
KERNEL_TOL = {"flash_attention": 1e-2, "ssd_scan": 1e-2, "rglru_scan": 1e-4}
# ||a - b|| / ||b|| over one position's logits. bf16 weights and activations
# against f32 leave ~1e-2; a wrong mask, position or cache slot leaves O(1).
REF_TOL = 5e-2        # engine prefill (bf16) vs f32 forward, first token
CACHE_TOL = 5e-2      # engine decode step at position p vs f32 forward at p


class SmokeFailure(RuntimeError):
    pass


def require(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def device_info() -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


# -- phase 1: kernels -------------------------------------------------------

def _flash_case(cfg, S: int, window: int):
    def inputs(key):
        kq, kk, kv = jax.random.split(key, 3)
        q = jax.random.normal(kq, (1, S, cfg.num_heads, cfg.head_dim))
        k = jax.random.normal(kk, (1, S, cfg.num_kv_heads, cfg.head_dim))
        v = jax.random.normal(kv, (1, S, cfg.num_kv_heads, cfg.head_dim))
        return q, k, v
    name = (f"flash_attention {cfg.name} H={cfg.num_heads} "
            f"kv={cfg.num_kv_heads} hd={cfg.head_dim} S={S} window={window}")
    return (name, "flash_attention", inputs,
            functools.partial(flash_attention, causal=True, window=window),
            functools.partial(ref.flash_attention_ref, causal=True,
                              window=window))


def _ssd_case(cfg, n_chunks: int):
    L, H = cfg.ssm_chunk, cfg.ssm_heads
    S, P, N = n_chunks * L, cfg.ssm_head_dim, cfg.ssm_state

    def inputs(key):
        kx, kd, ka, kb, kc = jax.random.split(key, 5)
        return (jax.random.normal(kx, (1, S, H, P)),
                jax.random.uniform(kd, (1, S, H), minval=1e-3, maxval=0.1),
                -jax.random.uniform(ka, (H,), minval=0.5, maxval=2.0),
                jax.random.normal(kb, (1, S, 1, N)),
                jax.random.normal(kc, (1, S, 1, N)))
    name = f"ssd_scan {cfg.name} heads={H} p={P} n={N} chunk={L} S={S}"
    return (name, "ssd_scan", inputs,
            functools.partial(ssd_scan, chunk=L),
            functools.partial(ref.ssd_scan_ref, chunk=L))


def _rglru_case(cfg, S: int):
    W = cfg.rnn_width

    def inputs(key):
        ka, kb = jax.random.split(key)
        return (jax.random.uniform(ka, (1, S, W), minval=0.7, maxval=0.999),
                jax.random.normal(kb, (1, S, W)))
    return (f"rglru_scan {cfg.name} W={W} S={S}", "rglru_scan", inputs,
            rglru_scan, ref.rglru_scan_ref)


def kernel_cases(reduced: bool = False) -> list:
    """The kernels of the served architectures at their widths: OLMo-1B
    attention at the frame prompt, at 200 (off the 128-row block grid) and
    at 2k, RecurrentGemma-9B's windowed MQA over twice its window, a
    Mamba-2-2.7B SSD layer and a RecurrentGemma-9B RG-LRU layer over 200
    steps. ``reduced`` keeps the code path at the reduced configs' widths
    for a CPU rehearsal."""
    olmo = get_config("olmo-1b", reduced=reduced)
    rg = get_config("recurrentgemma-9b", reduced=reduced)
    mamba = get_config("mamba2-2.7b", reduced=reduced)
    return [_flash_case(olmo, PROMPT_LEN, 0),
            _flash_case(olmo, 200, 0),
            _flash_case(olmo, 128 if reduced else 2048, 0),
            _flash_case(rg, 2 * rg.window, rg.window),
            _ssd_case(mamba, 4),
            _rglru_case(rg, 200)]


def phase_kernels(cases: list, *, interpret: bool = False) -> dict:
    errs = {}
    for i, (name, kind, inputs, kernel, oracle) in enumerate(cases):
        args = inputs(jax.random.PRNGKey(i))
        t0 = time.monotonic()
        got = jax.jit(functools.partial(kernel, interpret=interpret))(*args)
        got = jax.block_until_ready(got)
        secs = time.monotonic() - t0
        with jax.default_matmul_precision("highest"):
            want = jax.jit(oracle)(*args)
        err = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want)))
        rel = err / float(jnp.max(jnp.abs(want)))
        print(f"kernel {name}: max_abs_err={err:.3e} rel={rel:.3e} "
              f"tol={KERNEL_TOL[kind]:.0e} compile+run_s={secs:.2f}")
        require(bool(np.isfinite(rel)) and rel <= KERNEL_TOL[kind],
                f"{name}: error {rel:.3e} over tolerance {KERNEL_TOL[kind]}")
        errs[name] = rel
    return errs


# -- phase 2: serving -------------------------------------------------------

def phase_serving(*, reduced: bool = False) -> dict:
    out = serve(ARCH, n_streams=STREAMS, fps=FPS, seconds=SECONDS,
                reduced=reduced)
    print("serve report:", json.dumps(out))
    frames = int(STREAMS * FPS * SECONDS)
    print(f"serving {ARCH} reduced={reduced}: {out['frames_served']}/{frames} "
          f"frames, tokens per frame {out['tokens_per_frame']}, warmup "
          f"(compile + first drain) {out['warmup_s']} s, "
          f"{out['tokens_per_s']} tokens/s (one unbenchmarked smoke run, "
          f"not a device metric)")
    require(out["frames_served"] == frames,
            f"{out['frames_served']} of {frames} frame requests answered")
    require(out["tokens_per_frame"] == [NEW_TOKENS],
            f"tokens per frame {out['tokens_per_frame']} != [{NEW_TOKENS}]")
    require(out["tokens_per_s"] > 0, "no tokens/s measured")
    require(set(out["fleet_plans"]) == PLANS,
            f"fleet plans {sorted(out['fleet_plans'])}")
    return out


# -- phase 3: reference -----------------------------------------------------

def _fmt(a: np.ndarray) -> str:
    return np.array2string(a, precision=3, max_line_width=10**6)


def _rel_l2(a, b) -> np.ndarray:
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return np.linalg.norm(a - b, axis=-1) / np.linalg.norm(b, axis=-1)


def _tap(program, kind: str, log: list):
    """Stands in for one of the engine's compiled programs: calls it and
    logs, per call, the batch it was given, the slot (admissions only) and
    the logits it returned."""
    def call(params, cache, batch, *slot):
        logits, cache = program(params, cache, batch, *slot)
        # copies: on the CPU a batch array may alias the engine's own
        # numpy state, which the engine updates after the call
        log.append((kind, {k: np.array(v) for k, v in batch.items()},
                    int(slot[0]) if slot else None,
                    np.asarray(logits, np.float32)))
        return logits, cache
    return call


def _engine_logits(req: Request, log: list) -> np.ndarray:
    """One request's logits as the engine computed them, read off its
    program calls: the admission that prefilled this prompt, then the
    decode steps that followed, each of which must have fed the request's
    previous token at its next position in its slot, with no other
    admission into that slot meanwhile. Returns (tokens generated, V):
    the logits at positions L-1 .. L+n-2 of an L-token prompt."""
    rid, L, n = req.request_id, len(req.tokens), len(req.output)
    admits = [i for i, (kind, batch, _, _) in enumerate(log)
              if kind == "prefill"
              and np.array_equal(batch["tokens"][0], req.tokens)]
    require(len(admits) == 1, f"{rid}: admitted {len(admits)} times")
    i0 = admits[0]
    slot, out = log[i0][2], [log[i0][3]]
    for i in range(i0 + 1, len(log)):
        if len(out) == n:
            break
        kind, batch, s, logits = log[i]
        require(not (kind == "prefill" and s == slot),
                f"{rid}: slot {slot} re-admitted while it held the request")
        if kind == "decode":
            k = len(out) - 1
            fed = (int(batch["token"][slot]), int(batch["pos"][slot]))
            require(fed == (req.output[k], L + k),
                    f"{rid}: decode fed (token, pos) {fed} in slot {slot}, "
                    f"not {(int(req.output[k]), L + k)}")
            out.append(logits[slot])
    require(len(out) == n, f"{rid}: {len(out)} of {n} steps found")
    out = np.stack(out)
    require(np.array_equal(out.argmax(-1), req.output),
            f"{rid}: outputs are not the argmax of the engine's logits")
    return out


def phase_reference(*, reduced: bool = False) -> dict:
    """The engine, built as ``serve`` builds it, against an f32 ``jnp``
    forward of the same weights over what it generated."""
    cfg = get_config(ARCH, reduced=reduced)
    params = init_weights(cfg, reduced=reduced)
    eng = ContinuousBatchingEngine(cfg, params, max_slots=SLOTS,
                                   cache_len=REF_CACHE_LEN)
    prefill, log = eng._prefill_slot, []
    eng._prefill_slot = _tap(prefill, "prefill", log)
    eng._decode = _tap(eng._decode, "decode", log)
    rng = np.random.default_rng(REF_SEED)
    reqs = [Request(f"ref-{i}", rng.integers(0, cfg.vocab_size, L,
                                             dtype=np.int32),
                    max_new_tokens=n)
            for i, (L, n) in enumerate(REF_REQUESTS)]
    for r in reqs:
        eng.submit(r)
    t0 = time.monotonic()
    done = eng.drain()
    slots = sorted(s for kind, _, s, _ in log if kind == "prefill")
    print(f"reference: engine served {len(done)} requests in "
          f"{eng.stats['decode_steps']} decode steps (compiles included) "
          f"in {time.monotonic() - t0:.2f} s, use_kernels="
          f"{eng.opts.use_kernels}, admissions per slot "
          f"{np.bincount(slots, minlength=SLOTS).tolist()}")
    require(sorted(r.request_id for r in done) ==
            sorted(r.request_id for r in reqs), "requests lost")
    require(all(len(r.output) == r.max_new_tokens for r in reqs),
            f"tokens per request {[len(r.output) for r in reqs]}")
    require(max(np.bincount(slots)) > 1, "no slot was re-admitted")
    got = [_engine_logits(r, log) for r in reqs]

    # f32 jnp forward of the same weights over prompt + generated tokens,
    # kernels off, highest precision; right-padded to one length, which no
    # earlier position of a causal model sees
    seqs = [np.concatenate([r.tokens, r.output[:-1]]) for r in reqs]
    width = max(len(s) for s in seqs)
    tokens = np.stack([np.pad(s, (0, width - len(s))) for s in seqs])
    ref_opts = M.ModelOptions(remat=False, use_kernels=False)

    def forward(params, tokens):
        p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        h, _ = M.forward_hidden(p32, {"tokens": tokens}, cfg, ref_opts)
        return layers.unembed(p32["embed"], h, cfg)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.jit(forward)(params, jnp.asarray(tokens)))
    want = [want[i, len(r.tokens) - 1: len(seqs[i])]
            for i, r in enumerate(reqs)]
    ref_err = np.array([_rel_l2(g[0], w[0]) for g, w in zip(got, want)])
    cache_err = np.array([_rel_l2(g[1:], w[1:]).max()
                          for g, w in zip(got, want)])
    print(f"reference: engine first-token logits vs f32 forward: rel_l2 "
          f"{_fmt(ref_err)} tol={REF_TOL:.0e}")
    print(f"reference: engine decode logits vs f32 forward, worst step per "
          f"request: rel_l2 {_fmt(cache_err)} "
          f"tol={CACHE_TOL:.0e}")
    require(bool(np.all(ref_err <= REF_TOL)),
            f"engine prefill vs f32 forward rel_l2 {ref_err} over {REF_TOL}")
    require(bool(np.all(cache_err <= CACHE_TOL)),
            f"engine decode vs f32 forward rel_l2 {cache_err} over "
            f"{CACHE_TOL}")

    # the engine's admission program carries the compiled Pallas kernel
    has_kernel = {}
    for L in sorted({L for L, _ in REF_REQUESTS}):
        text = prefill.lower(params, eng.cache,
                             {"tokens": jnp.zeros((1, L), jnp.int32)},
                             jnp.asarray(0, jnp.int32)).compile().as_text()
        has_kernel[L] = "tpu_custom_call" in text
    if jax.default_backend() == "tpu":
        print(f"reference: tpu_custom_call in the compiled prefill, by "
              f"prompt length: {has_kernel}")
        require(all(has_kernel.values()),
                "the served prefill contains no Pallas kernel")
    return {"ref_rel_l2": ref_err, "cache_rel_l2": cache_err,
            "has_kernel": has_kernel}


def main() -> int:
    dev = device_info()
    print("device:", json.dumps(dev))
    if dev["platform"] != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev['platform']!r}); "
              f"this script runs on the chip only and has no CPU fallback",
              file=sys.stderr)
        return 1
    print("compile cache:", enable_compile_cache())
    t0 = time.monotonic()
    phase_kernels(kernel_cases())
    print(f"phase 1 kernels done at {time.monotonic() - t0:.1f} s")
    phase_serving()
    print(f"phase 2 serving done at {time.monotonic() - t0:.1f} s")
    phase_reference()
    print(f"phase 3 reference done at {time.monotonic() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
