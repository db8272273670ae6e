"""The one file of the benchmark that touches the program under test.

It builds the program's own objects from a configuration file (`Trainer`,
`DecodeEngine`; the model object and the map from the seeded leaves to
the program's parameter tree are the family's, `families/<model_type>.py`),
lays the benchmark's seeded weights out the way the program wants them,
and reads the program's counters. Nothing here measures anything. Faults
for `prove.py` and the tests are planted here, underneath the timed path.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from . import families, weights

_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}


def enable_compile_cache():
    """The program's own persistent cache (`<checkout>/.jax_cache`, or
    where JAX_COMPILATION_CACHE_DIR points), with every program kept,
    however small or quick to compile."""
    from megatron_llm_tpu.utils.compile_cache import enable_compile_cache

    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # an executable from the cache carries the names (scopes, source
    # lines) of the build that stored it; with the metadata in the key a
    # traced run reads its own build's names, and the untraced runs of
    # the same checkout share its entries
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    return path


def _set(tree: dict, path, value):
    for k in path[:-1]:
        tree = tree.setdefault(k, {})
    tree[path[-1]] = value


def _get(tree: dict, path):
    for k in path:
        tree = tree[k]
    return tree


def program_tree(cfg: dict, stacks: dict, glob: dict) -> dict:
    """Neutral leaves -> the program's parameter tree (same arrays), by
    the family's paths. `stacks`: {kind: that kind's stacked leaves}
    (`weights.make_stacked`); each goes under `layers` whole."""
    fam = families.find(cfg)
    out = {}
    for kind, stack in stacks.items():
        for name, path in fam.layer_paths(cfg, kind).items():
            _set(out, ("layers",) + path, stack[name])
    for name, path in fam.global_paths(cfg).items():
        _set(out, path, glob[name])
    return out


def neutral_leaves(cfg: dict, tree: dict, layers: int):
    """The program's tree -> ({kind: {neutral name: leaf}}, {neutral
    name: global leaf}), what `program_tree` was given; a kind's leaves
    keep their leading axis, one entry for each of its layers."""
    fam = families.find(cfg)
    stacks = {kind: {name: _get(tree, ("layers",) + path)
                     for name, path in fam.layer_paths(cfg, kind).items()}
              for kind in weights.by_kind(cfg, layers)}
    glob = {name: _get(tree, path)
            for name, path in fam.global_paths(cfg).items()}
    return stacks, glob


# ---------------------------------------------------------------- training


class TrainProgram:
    """`Trainer` + its state, built once and handed to the window."""

    def __init__(self, cfg: dict, seed: int, chips: int,
                 mark=lambda name: None):
        from megatron_llm_tpu.config import ParallelConfig, TrainConfig
        from megatron_llm_tpu.parallel import initialize_parallel
        from megatron_llm_tpu.training.trainer import Trainer

        use = cfg["train"]
        tp = use["tensor_parallel"]
        if tp > chips:
            raise ValueError("configuration needs more chips than the cell")
        self.cfg, self.use, self.seed = cfg, use, seed
        self.family = fam = families.find(cfg)
        self.layers = use["num_hidden_layers"]
        if tp > 1:
            initialize_parallel(tp=tp,
                                sequence_parallel=use["sequence_parallel"])
        pcfg = ParallelConfig(tensor_parallel_size=tp,
                              sequence_parallel=use["sequence_parallel"],
                              num_microbatches=1)
        tcfg = TrainConfig(
            micro_batch_size=use["micro_batch_size"],
            global_batch_size=use["global_batch_size"],
            train_iters=10**9, optimizer=use["optimizer"], lr=use["lr"],
            min_lr=use["lr"], lr_decay_style=use["lr_decay_style"],
            lr_warmup_iters=0, weight_decay=use["weight_decay"],
            clip_grad=use["clip_grad"], adam_beta1=use["adam_beta1"],
            adam_beta2=use["adam_beta2"], adam_eps=use["adam_eps"],
            bf16=use["compute_dtype"] == "bfloat16", seed=seed % (2**31),
            **fam.trainer_args(cfg, use))
        self.model = fam.model(cfg, use, tp)
        self.trainer = Trainer(self.model, tcfg, pcfg)
        mark("trainer_made")
        self.state = self.trainer.setup()
        jax.block_until_ready(self.state.params)
        mark("trainer_setup")
        # the benchmark's own seeded weights take the place of model.init's
        shardings = jax.tree.map(lambda x: x.sharding, self.state.params)
        self.state.params = None
        self._make_params = jax.jit(self._seeded_params,
                                    out_shardings=shardings)
        self._words = weights.seed_words(seed)
        self.state.params = jax.block_until_ready(
            self._make_params(self._words))
        mark("seeded_weights")
        self.fault = None
        self._stats = {}

    def _seeded_params(self, words):
        tree = program_tree(
            self.cfg, weights.make_stacked(self.cfg, words, self.layers),
            weights.make_globals(self.cfg, words))
        dt = _DTYPES[self.use["params_dtype"]]
        return jax.tree.map(lambda x: x.astype(dt), tree)

    def step(self, text: np.ndarray) -> float:
        """One optimizer step through `Trainer.train_step`, fenced on the
        loss the way `Trainer.train` fences."""
        if self.fault == "half_batch":
            # half of the rows left out, the mean taken over the rest
            half = text.shape[1] // 2
            text = np.concatenate([text[:, :half], text[:, :half]], axis=1)
        if self.fault == "no_exchange":
            # one rank's partial products, never summed with the others'
            self.state.params = self._one_ranks_rows(self.state.params)
        if self.fault == "state_unchanged":
            keep = jax.tree.map(jnp.copy, (self.state.params,
                                           self.state.opt_state))
        stats = self._stats = self.trainer.train_step(self.state, text)
        loss = float(stats["loss"])
        if self.fault == "state_unchanged":
            self.state.params, self.state.opt_state = keep
        return loss

    def stats(self) -> dict:
        """What the last step's stats carry beside the loss, as numbers.
        Read ONCE, after the window has closed: each is a fetch."""
        out = {}
        for name, value in self._stats.items():
            if name != "loss" and np.ndim(value) == 0:
                try:
                    out[name] = float(value)
                except (TypeError, ValueError):
                    pass
        return out

    def _one_ranks_rows(self, params):
        from .check import one_ranks_share

        tp = self.use["tensor_parallel"]
        fam, cfg = self.family, self.cfg

        def cut(p):
            out = jax.tree.map(lambda x: x, p)  # fresh dicts, same leaves
            for kind in weights.by_kind(cfg, self.layers):
                for name, path in fam.layer_paths(cfg, kind).items():
                    if name in fam.ROW_PARALLEL:
                        # stacked: the leading axis is the kind's layers
                        path = ("layers",) + path
                        _set(out, path, one_ranks_share(
                            _get(p, path), tp, fam.ROW_PARALLEL[name] + 1))
            return out

        shardings = jax.tree.map(lambda x: x.sharding, params)
        return jax.jit(cut, donate_argnums=0, out_shardings=shardings)(params)

    def first_moment_norms(self) -> dict:
        """Per-leaf (and per-block) L2 norms of Adam's first moment."""
        return _leaf_norms(self.cfg, self.state.opt_state.m, self.layers)

    def change_norms(self) -> dict:
        """Per-leaf norms of (parameters now - the seeded parameters), in
        one program, so the difference itself is never held."""
        cfg, layers = self.cfg, self.layers

        def norms(p, words):
            diff = jax.tree.map(
                lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
                p, self._seeded_params(words))
            return _norms_of(cfg, diff, layers)

        got = jax.jit(norms)(self.state.params, self._words)
        return {k: np.asarray(v) for k, v in got.items()}

    def kernel_fallbacks(self) -> int:
        return kernel_fallbacks()

    def free(self):
        from megatron_llm_tpu.parallel.mesh import destroy_parallel

        for leaf in jax.tree.leaves((self.state.params,
                                     self.state.opt_state)):
            leaf.delete()
        self.state = self.trainer = None
        destroy_parallel()


def _norms_of(cfg: dict, tree: dict, layers: int) -> dict:
    """The program's tree -> {neutral name: norms}: for a block leaf one
    entry for each block that has a leaf of that name, in layer order
    (two kinds that share a name interleave), for a global leaf one.
    Traced."""
    stacks, glob = neutral_leaves(cfg, tree, layers)
    groups = weights.by_kind(cfg, layers)
    found = {}  # name -> [(the layers of a kind that has it, their norms)]
    for kind, stack in stacks.items():
        for name, x in stack.items():
            x = jnp.square(x.astype(jnp.float32))
            found.setdefault(name, []).append(
                (groups[kind],
                 jnp.sqrt(jnp.sum(x.reshape(x.shape[0], -1), axis=1))))
    out = {}
    for name, parts in found.items():
        order = np.argsort(np.concatenate([idx for idx, _ in parts]))
        out[name] = jnp.concatenate([n for _, n in parts])[order]
    for name, x in glob.items():
        out[name] = jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))[None]
    return out


def _leaf_norms(cfg: dict, tree: dict, layers: int) -> dict:
    got = jax.jit(lambda tree: _norms_of(cfg, tree, layers))(tree)
    return {k: np.asarray(v) for k, v in got.items()}


def kernel_fallbacks() -> int:
    from megatron_llm_tpu.ops import dispatch

    return len(dispatch.fallbacks())


# ----------------------------------------------------------------- serving


class _StackView:
    """A 'stacked' leaf that is really one standalone buffer per block:
    `view[j]` is the array of the j-th block of its kind. The program's
    `prepare_decode_params` slices `x[j]` out of a stacked tree, which
    for a model that fills the chip would hold every block twice; this
    hands it the per-block buffers it is about to make. Each is handed
    out ONCE and not kept: where the program cuts a layout of its own
    from a leaf, the seeded one goes as soon as the program lets go of
    it (unless the caller of `ServeProgram` holds the weights itself)."""

    def __init__(self, per_layer):
        self._per_layer = dict(enumerate(per_layer))

    def __getitem__(self, j):
        return self._per_layer.pop(j)


class ServeProgram:
    """A started `DecodeEngine` over seeded weights."""

    def __init__(self, cfg: dict, seed: int, use: dict | None = None,
                 made=None, mark=lambda name: None):
        from megatron_llm_tpu.inference.engine import DecodeEngine

        use = use or cfg["serve"]
        self.cfg, self.use, self.seed = cfg, use, seed
        fam = families.find(cfg)
        self.layers = use["num_hidden_layers"]
        self.model = fam.model(cfg, use)
        # `made`: weights the caller holds and keeps (the sweep builds
        # several engines over one set); made here, they are the engine's
        # alone once it is built
        per_layer, glob = made or self.make_weights(cfg, seed, use)
        jax.block_until_ready(per_layer)
        mark("seeded_weights")
        stacks = {kind: {name: _StackView([per_layer[i][name] for i in idx])
                         for name in per_layer[idx[0]]}
                  for kind, idx in weights.by_kind(cfg, self.layers).items()}
        params = program_tree(cfg, stacks, glob)
        del per_layer, glob, stacks
        self.engine = DecodeEngine(
            self.model, params, slots=use["slots"],
            page_size=use["page_size"], max_context=use["max_context"],
            max_queue=use["max_queue"], step_horizon=use["step_horizon"],
            prefill_chunk_tokens=use["prefill_chunk_tokens"],
            prefix_cache=use["prefix_cache"], kv_dtype=use["kv_dtype"],
            warmup_compile=False, termination_id=None,
            vocab_size=cfg["vocab_size"], **fam.engine_args(cfg, use))
        del params
        mark("engine_built")
        self.engine.warmup()  # exactly this engine's own buckets
        mark("engine_warmed")
        self.engine.start()
        self.fault = None
        self._booked = 0

    @staticmethod
    def make_weights(cfg: dict, seed: int, use: dict):
        """(per-block leaves in layer order, global leaves) in the served
        type: one compiled program a kind of block, run once per block,
        on the device."""
        dt = _DTYPES[use["weights_dtype"]]
        words = weights.seed_words(seed)
        L = use["num_hidden_layers"]
        served = lambda t: jax.tree.map(  # noqa: E731
            lambda x: x.astype(dt), t)
        fam = families.find(cfg)
        make_layer = weights.layer_makers(cfg, L, finish=served)
        per_layer = [make_layer[fam.layer_kind(cfg, i)](words, jnp.int32(i))
                     for i in range(L)]
        glob = jax.jit(lambda w: served(weights.make_globals(cfg, w)))(words)
        return per_layer, glob

    def plant(self, fault: str):
        """A token altered where it is produced: every 7th booked token
        is replaced by its neighbour in the vocabulary."""
        assert fault == "token_altered", fault
        self.fault = fault
        book = self.engine._book_token
        vocab = self.cfg["vocab_size"]

        def altered(i, tok, now=None):
            self._booked += 1
            if self._booked % 7 == 0:
                tok = (tok + 1) % vocab
            return book(i, tok, now)

        self.engine._book_token = altered

    def submit(self, prompt, n_out: int):
        """What `server.py` does for one streamed request."""
        return self.engine.submit(
            list(prompt), n_out, top_k=1,
            use_eod_for_early_termination=False, stream=True)

    def counters(self) -> dict:
        """Six short names the harness itself reads, and every numeric
        key of `DecodeEngine.counters()` under the engine's own name."""
        c = self.engine.counters()
        out = {
            "steps": c["serve_steps"],
            "prefill_tokens": c["serve_prefill_tokens"],
            "admitted": c["serve_admitted"],
            "retired": c["serve_retired"],
            "occupancy": c["serve_slot_occupancy"],
            "queue_depth": c["serve_queue_depth"],
        }
        out.update({k: v for k, v in c.items()
                    if isinstance(v, (int, float))
                    and not isinstance(v, bool)})
        return out

    def kernel_fallbacks(self) -> int:
        return kernel_fallbacks()

    def stop(self):
        """Ends the serve loop; what is still running or queued fails."""
        self.engine.stop(drain=False)

    def free(self, keep_weights: bool = False):
        eng = self.engine
        eng.stop(drain=False)
        trees = [eng._pools_k, eng._pools_v, eng._last_logits]
        if not keep_weights:
            trees.append(eng._dec_params)
        self.engine = eng = None
        for leaf in jax.tree.leaves(trees):
            if isinstance(leaf, jax.Array) and not leaf.is_deleted():
                leaf.delete()
