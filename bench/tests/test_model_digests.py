"""The model modules make the weights and the reference's logits that the
benchmark made before the model description moved into
``bench/models/``, bit for bit, at each configuration's rehearsal size.

The digests were made on the commit before that move, with the same probe
and the same seed, through ``weights.make_weights(model, seed, dtype)``
and ``reference.logits(params, model, tokens, rows, cols[, quant])``.
"""
import hashlib
import json
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import harness, reference, weights

ROOT = Path(__file__).resolve().parents[2]
SEED = 2**32 + 16
DIGESTS = {
    "olmo-1b": {
        "weights": "34a5ea2da78c3ed86b2bd6ef1a1590a6c039990d2dd5d7856c45b94fd7cbcb9b",
        "logits": "0bfd0e7d20e599f9c68f511296c9db9e5b9b9863c7686126dd613f88ada26a38",
        "logits_fp8": "80636f06b942883b855b7e951bb1e799b1feddd04f4fc989fe71a05f12902d07",
    },
    "mamba2-2.7b": {
        "weights": "0a37542271a901b9ecbb0f4c5fd2307b3496bce9fc0b7d876cddf1410c394377",
        "logits": "2f1d7c24a6b5387ff0b6a2cde68666c36049e9e63ba97c32d5a4714a34f788a6",
        "logits_fp8": "08307f81d6a92b5e31a05b44adebcac71dd1d56e72d12dc58c3a1ec626bc334a",
    },
}


def tree_digest(params) -> str:
    """sha256 over every leaf's path, dtype, shape and bytes."""
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        a = np.asarray(leaf)
        h.update(f"{jax.tree_util.keystr(path)}:{a.dtype}:{a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


def probe(token_vocab: int, length: int):
    """Eight rows of seed-drawn tokens; four positions in each, first to
    last."""
    rng = np.random.default_rng(SEED)
    toks = rng.integers(0, token_vocab, (8, length)).astype(np.int32)
    rows = np.repeat(np.arange(8), 4).astype(np.int32)
    cols = np.tile(np.array([0, length // 3, 2 * length // 3, length - 1]),
                   8).astype(np.int32)
    return toks, rows, cols


@pytest.mark.parametrize("config", sorted(DIGESTS))
def test_weights_and_reference_logits_are_unmoved(config):
    conf = json.loads((ROOT / "bench" / "configs" / f"{config}.json").read_text())
    sizes = conf["rehearsal"]
    m = sizes["model"]
    mod = harness.model_module(config)
    params = weights.make_weights(mod, m, SEED, conf["dtype"])
    toks, rows, cols = probe(sizes["token_vocab"], 40)
    f32 = np.asarray(reference.logits(params, mod, m, toks, rows, cols))
    f8 = np.asarray(reference.logits(params, mod, m, toks, rows, cols,
                                     quant="fp8"))
    got = {"weights": tree_digest(params),
           "logits": hashlib.sha256(f32.tobytes()).hexdigest(),
           "logits_fp8": hashlib.sha256(f8.tobytes()).hexdigest()}
    assert got == DIGESTS[config]


def test_a_configuration_without_a_model_module_is_refused():
    with pytest.raises(harness.Refused, match="no model module"):
        harness.model_module("no-such-config")
