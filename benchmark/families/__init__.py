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

  seeded weights   `layer_shapes(cfg, layer)`, `global_shapes(cfg)`,
                   `draw(key, name, shape, cfg)`: the neutral leaves that
                   `weights.py` draws for reference and program alike;
                   `layer_kind(cfg, layer)`: layers of one kind have the
                   same leaves and share a compiled reference block
  into the program `model(cfg, use, tp)`, `layer_paths(cfg)`,
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
