"""Random weights from the seed, in the parameter layout the program takes,
made on the device in one jitted call in the type they are served in.

The benchmark makes the weights itself so that its reference takes nothing
the program made. What the weights are is the configuration's model module
(``bench/models/<config>.py``), found by name: its ``make(key, m, dtype)``
returns the tree, drawing each leaf with ``normal``. This file holds only
what every configuration shares: the seed's key, the draw, the one jitted
call, and the check of the layout against the program's own
``init_params`` by shape (``check_layout``), never by value.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def seed_key(seed: int, stream: str):
    """A PRNG key for one use of a seed of any size."""
    words = [int(b) for b in stream.encode()]
    state = np.random.SeedSequence([int(seed), *words]).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(state, jnp.uint32))


def normal(key, n: tuple, shape: tuple, dtype, scale: float):
    """Normal(0, scale) of shape ``n + shape``; a stacked leaf (``n`` set)
    is drawn one layer at a time so that no float32 draw of the whole
    stack is ever held."""
    draw = lambda k: jax.random.normal(k, shape, dtype) * scale
    if not n:
        return draw(key)
    return jax.lax.map(draw, jax.random.split(key, n[0]))


def _frozen(m: dict):
    return tuple(sorted((k, tuple(map(tuple, v)) if k == "block_pattern" else v)
                        for k, v in m.items()))


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _make_jit(key, mod, frozen, dtype):
    m = {k: [list(x) for x in v] if k == "block_pattern" else v
         for k, v in frozen}
    return mod.make(key, m, dtype)


def make_weights(mod, model: dict, seed: int, dtype: str):
    """All weights of ``model`` from ``seed``, in ``dtype``, on the device,
    as the model module ``mod`` lays them out."""
    return _make_jit(seed_key(seed, "weights"), mod, _frozen(model),
                     DTYPES[dtype])


def check_layout(params, program_shapes) -> None:
    """Raise unless ``params`` has the tree, shapes and dtypes of the
    program's own initialiser (``jax.eval_shape`` of it)."""
    got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    want = jax.tree.map(lambda a: (a.shape, a.dtype), program_shapes)
    if jax.tree.structure(params) != jax.tree.structure(program_shapes) \
            or got != want:
        raise RuntimeError(f"weight layout differs from the program's:\n"
                           f"benchmark {got}\nprogram {want}")
