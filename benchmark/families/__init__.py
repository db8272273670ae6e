"""What the harness knows about an architecture lives in one module a
family, found by the configuration's `model_type`:

  families/<model_type>.py     seeded weights, the way into the program,
                               the work (this package)
  reference/<model_type>.py    the family's plain reference

No other module of the benchmark names a family. A later PR adds one by
adding those two files and its data files; nothing that is there is
edited. `find(cfg)` returns the family's module with its reference as
`.reference`.

A family module holds (see `falcon.py`, the first one):

  seeded weights   `layer_kind(cfg, layer)`, `layer_shapes(cfg, layer)`,
                   `global_shapes(cfg)`, `draw(key, name, shape, cfg)`:
                   the neutral leaves that `weights.py` draws for
                   reference and program alike
  into the program `model(cfg, use, tp)`, `layer_paths(cfg, kind)`,
                   `global_paths(cfg)` (neutral leaf -> the program's
                   parameter tree), `trainer_args(cfg, use)`,
                   `engine_args(cfg, use)`, `ROW_PARALLEL` (the leaves
                   whose product tensor parallelism sums over the ranks:
                   where `prove.py`'s `no_exchange` fault is planted)
  its work         `train_flops_per_token`, `serve_span_flops` (what the
                   cells' runners call for the whole step's share of the
                   peak), every `fn(cfg, use, traced)` a `site_roofline`
                   metric file names as `flops_fn` / `bytes_fn` (a
                   kernel's FLOPs and bytes from the traced part's
                   counters), and the counts its configuration files
                   quote (`n_params`, `kv_bytes_per_token`, ...)
  its reference    `embed`, `block(w, x, cfg, positions, matmul, layer=i)`,
                   `final_logits`, `mean_loss` (cross-entropy and every
                   other term of the family's loss)

The blocks of a model may be of several KINDS (one with attention and one
without, a dense MLP and a routed one): a block's leaves, their shapes,
their paths into the program and which of them are row-parallel are its
kind's. The kind is static. A family whose blocks are all alike has one
kind and is the case n = 1 of what follows; the harness has no other path
for it.

  1. `layer_kind(cfg, layer)` gets a Python integer, the block's index
     among the cell's layers, and returns a hashable. `layer_shapes(cfg,
     layer)` is only ever called with a Python integer and gives the
     leaves of that layer's kind. `weights.by_kind` groups the cell's
     layers by kind, in layer order.
  2. `weights.make_layer` draws block i from the shapes of its kind with
     the key `fold_in(key(seed), 1 + i)`, i counted over ALL the layers,
     its leaves numbered in the sorted order of that block's own names.
     The index may be a traced argument, so whatever is compiled is
     compiled once a kind (`weights.layer_makers`: the reference's
     blocks, the served weights), never once a layer. Stacks are one a
     kind (`weights.make_stacked`): entry j of a kind's stack is that
     kind's j-th layer.
  3. `layer_paths(cfg, kind)` maps that kind's leaves to their paths
     under the program's `layers`; a kind's stack (an array with a
     leading axis in training, a view of per-block buffers in serving)
     goes there whole. The paths of a family of several kinds begin
     with the name the program gives that kind's stack, so the
     program's tree holds one stack a kind. `ROW_PARALLEL` names a leaf
     and the axis the ranks split, for every kind that has a leaf of
     that name; a block's row-parallel leaves are those of its own.
  4. Whatever is read leaf by leaf (norms of gradients, moments and
     changes, on the program's side and the reference's alike) has, for
     each leaf name, one entry for each block THAT HAS a leaf of that
     name, in layer order. Two kinds may use one name (their entries
     then interleave by layer) or different ones.
  5. The reference's `block` gets its layer's index as `layer=i` and
     picks its kind by it; layers of one kind share one compiled block.
"""

from __future__ import annotations

import hashlib
import importlib.util
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
OWN = os.path.dirname(HERE)  # this tree's `benchmark/`
_IN_USE = [OWN]  # the copy of the benchmark whose cell is being run
_FOUND = {}


def _load(package: str, name: str, base: str):
    """The module `<base>/<package>/<name>.py`. Loaded from its path: a
    rehearsal's copy of the benchmark brings families that this tree does
    not have. This tree's module is `benchmark.<package>.<name>`, the one
    an import gives; a copy's gets a name of its own beside it, so two
    copies in one process never hand each other a family."""
    qualified = f"benchmark.{package}.{name}"
    if base != OWN:
        qualified += "__" + hashlib.sha1(base.encode()).hexdigest()[:8]
    if qualified in sys.modules:
        return sys.modules[qualified]
    path = os.path.join(base, package, name + ".py")
    if not os.path.exists(path):
        raise SystemExit(f"model_type {name!r}: no {package}/{name}.py "
                         f"under {base}")
    spec = importlib.util.spec_from_file_location(qualified, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[qualified] = module
    spec.loader.exec_module(module)
    return module


def find(cfg: dict, base: str | None = None):
    """The family of a configuration, by its `model_type`. `base`: the
    copy of the benchmark to look in (`harness.load_cell` names the
    cell's); a call without one means the copy named last."""
    if base:
        _IN_USE[0] = os.path.abspath(base)
    key = (_IN_USE[0], cfg["model_type"])
    if key not in _FOUND:
        family = _load("families", key[1], key[0])
        family.reference = _load("reference", key[1], key[0])
        _FOUND[key] = family
    return _FOUND[key]
