"""Weights from `--seed`, made on the device, in the benchmark's own
neutral layout. The program's adapter (`program.py`) and the plain
reference both draw from here, so neither takes anything the other has
made. Which leaves a block and the globals have, their shapes and how
each is drawn are the family's (`families/<model_type>.py`:
`layer_shapes`, `global_shapes`, `draw`); the keys are made here, the
same way for every family.
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


def make_layer(cfg: dict, seed, layer) -> dict:
    """One block's float32 leaves. `layer` may be a traced integer where
    the family's blocks are all of one kind."""
    fam = families.find(cfg)
    return _draw_all(fam, cfg, jax.random.fold_in(_key(seed), 1 + layer),
                     fam.layer_shapes(cfg, layer))


def make_globals(cfg: dict, seed) -> dict:
    fam = families.find(cfg)
    return _draw_all(fam, cfg, jax.random.fold_in(_key(seed), 0),
                     fam.global_shapes(cfg))


def make_stacked(cfg: dict, seed, layers: int) -> dict:
    """All blocks with a leading layer axis (what a layer scan reads)."""
    return jax.vmap(lambda i: make_layer(cfg, seed, i))(jnp.arange(layers))
