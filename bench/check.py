"""The comparison that decides ``correct`` for a served model.

Once the window has closed and the program's state is freed, a sample of
the finished requests, drawn from the seed and holding the one with the
most served tokens, is run through the plain reference (``bench.reference``,
with the configuration's model module) over each prompt with its served
tokens. At each served position the gap is the reference's best logit
minus the reference's logit of the token served. The widest gap over the
sample is the number compared with the cell's limit. Greedy decoding
serves the argmax, so a sound program reads a gap only where its rounding
flips a near tie.

The control puts the reference computed in float8 in the program's place:
at the same positions it takes the token the float8 logits put first and
reads that token's gap in the same way.
"""
from __future__ import annotations

import numpy as np

from bench import reference
from bench.traffic import rng_for

SAMPLE_TOKENS = 320          # served tokens the sample reaches at least
ROWS = 8                     # requests per reference call


def sample(records: list, seed: int, target: int = SAMPLE_TOKENS) -> list:
    """Finished records: the longest first, then a seed-drawn order, until
    ``target`` served tokens are covered."""
    done = [r for r in records if r.finished]
    if not done:
        return []
    longest = max(range(len(done)),
                  key=lambda i: (len(done[i].request.output), -i))
    order = [longest] + [i for i in rng_for(seed, "sample").permutation(len(done))
                         if i != longest]
    out, n = [], 0
    for i in order:
        if n >= target:
            break
        out.append(done[i])
        n += len(done[i].request.output)
    return out


def gaps(params, mod, model: dict, records: list, prompt_len: int,
         max_new: int, control: bool = False) -> dict:
    """Gaps of the served tokens (and of the control's tokens) of
    ``records``, through the model module ``mod``. Every call has
    ``ROWS`` rows of the mix's longest sequence, so one compile serves
    every run of a cell."""
    served, ctrl = [], []
    max_len, width = prompt_len + max_new - 1, ROWS * max_new
    for b in range(0, len(records), ROWS):
        block = records[b:b + ROWS]
        toks = np.zeros((ROWS, max_len), np.int32)
        rows = np.zeros(width, np.int32)
        cols = np.zeros(width, np.int32)
        want, k = [], 0
        for i, r in enumerate(block):
            out = np.asarray(r.request.output, np.int32)
            L, n = len(r.request.tokens), len(out)
            seq = np.concatenate([r.request.tokens, out[:-1]])
            toks[i, :len(seq)] = seq
            rows[k:k + n], cols[k:k + n] = i, np.arange(L - 1, L - 1 + n)
            want.append(out)
            k += n
        want = np.concatenate(want)
        ref = np.asarray(reference.logits(params, mod, model, toks, rows,
                                          cols))[:k]
        best = ref.max(-1)
        served.append(best - ref[np.arange(k), want])
        if control:
            low = np.asarray(reference.logits(params, mod, model, toks, rows,
                                              cols, quant="fp8"))[:k]
            ctrl.append(best - ref[np.arange(k), low.argmax(-1)])
    cat = lambda a: np.concatenate(a) if a else np.zeros(0)
    out = {"served": cat(served), "tokens": int(sum(len(a) for a in served))}
    if control:
        out["control"] = cat(ctrl)
    return out
