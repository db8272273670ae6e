"""Weights from `--seed`, made on the device, in the benchmark's own
neutral layout. The program's adapter (`program.py`) and the plain
reference both draw from here, so neither takes anything the other has
made. Which leaves a block and the globals have, their shapes and how
each is drawn are the family's (`families/<model_type>.py`:
`layer_kind`, `layer_shapes`, `global_shapes`, `draw`); the keys are made
here, the same way for every family.

A block's leaves are those of its KIND (`families/__init__.py` has the
contract): the cell's layers are grouped by kind, in layer order, and
whatever is compiled or stacked is compiled or stacked once a kind. A
family whose blocks are all alike is the case of one kind.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import families


def seed_words(seed: int) -> np.ndarray:
    """`--seed` as two 32-bit words, to hand to a compiled program as an
    ARGUMENT: a seed baked into a program makes every new seed a new
    program to compile. Seeds run a little past 2**31."""
    seed = int(seed)
    return np.array([seed & 0xFFFFFFFF, seed >> 32], np.uint32)


def _key(seed):
    """`seed`: a whole number, or its `seed_words` (traced or not)."""
    words = seed_words(seed) if isinstance(seed, int) else seed
    return jax.random.fold_in(jax.random.key(words[0]), words[1])


def _draw_all(fam, cfg: dict, key, shapes: dict) -> dict:
    return {name: fam.draw(jax.random.fold_in(key, i), name, shape, cfg)
            for i, (name, shape) in enumerate(sorted(shapes.items()))}


def by_kind(cfg: dict, layers: int) -> dict:
    """{kind: its layers' indices, ascending}; the kinds in the order of
    their first layer."""
    fam = families.find(cfg)
    groups = {}
    for i in range(layers):
        groups.setdefault(fam.layer_kind(cfg, i), []).append(i)
    return groups


def make_layer(cfg: dict, seed, layer, like: int | None = None) -> dict:
    """One block's float32 leaves: the shapes of its kind, the key of its
    index among all the layers. `layer` may be a traced integer; `like`
    then names a layer of the same kind (the family is only ever asked
    with a Python integer)."""
    fam = families.find(cfg)
    return _draw_all(fam, cfg, jax.random.fold_in(_key(seed), 1 + layer),
                     fam.layer_shapes(cfg, layer if like is None else like))


def layer_makers(cfg: dict, layers: int, finish=lambda leaves: leaves,
                 shardings=lambda like: None) -> dict:
    """{kind: compiled (seed's words, layer index) -> `finish`(that
    block's leaves)}: ONE program a kind, whose index is an argument.
    `shardings(like)`: where the leaves of the kind of layer `like` go."""
    return {kind: jax.jit(lambda w, i, like=idx[0]: finish(
                make_layer(cfg, w, i, like)), out_shardings=shardings(idx[0]))
            for kind, idx in by_kind(cfg, layers).items()}


def make_globals(cfg: dict, seed) -> dict:
    fam = families.find(cfg)
    return _draw_all(fam, cfg, jax.random.fold_in(_key(seed), 0),
                     fam.global_shapes(cfg))


def make_stacked(cfg: dict, seed, layers: int) -> dict:
    """{kind: its blocks' leaves with a leading axis (what a layer scan
    reads)}: entry j of a kind's stack is that kind's j-th layer."""
    return {kind: jax.vmap(lambda i, like=idx[0]: make_layer(
                cfg, seed, i, like))(jnp.asarray(idx, jnp.int32))
            for kind, idx in by_kind(cfg, layers).items()}
