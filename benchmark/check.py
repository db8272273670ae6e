"""What decides `correct`: the timed path's own output against the plain
reference (the family's, `reference/<model_type>.py`, over the shared
arithmetic of `reference/common.py`), number by number, each under a limit
of its own (`limits/<cell>.json`, set from chip readings: PERF.md).

Training: the losses of the first three steps, the first gradient as the
optimizer got it (Adam's first moment after one step), and the change of
the parameters after three steps, by the worst leaf. A leaf is one block's
matrix or vector, or a global one.
Serving: over a sample of finished requests, the widest gap by which a
served token's logit lies below the reference's best.

The control is the same reference computed in int8 (both operands of
every matrix product rounded to 8 bits with one scale per operand, the
cotangents too), put in the program's place. It never runs in a
benchmark run: `prove.py` and the tests run it.
"""

from __future__ import annotations

import sys

import numpy as np

import jax
import jax.numpy as jnp

from . import families, weights
from .reference import common as ref


# ------------------------------------------------------------ the control


def _fake_int8(x):
    scale = jnp.max(jnp.abs(x)) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


@jax.custom_vjp
def int8_matmul(a, b):
    return ref.f32_matmul(_fake_int8(a.astype(jnp.float32)),
                          _fake_int8(b.astype(jnp.float32)))


def _int8_fwd(a, b):
    return int8_matmul(a, b), (a, b)


def _int8_bwd(res, g):
    a, b = res
    qa = _fake_int8(a.astype(jnp.float32))
    qb = _fake_int8(b.astype(jnp.float32))
    qg = _fake_int8(g)
    da = ref.f32_matmul(qg, jnp.swapaxes(qb, -1, -2))
    db = ref.f32_matmul(jnp.swapaxes(qa, -1, -2), qg)
    # a batched left operand against an unbatched right one
    while db.ndim > b.ndim:
        db = db.sum(axis=0)
    return da.astype(a.dtype), db.astype(b.dtype)


int8_matmul.defvjp(_int8_fwd, _int8_bwd)

MATMULS = {"float32": ref.f32_matmul, "int8": int8_matmul}


# -------------------------------------------------------------- training


def _leaf_norms(tree: dict) -> dict:
    return {k: jnp.sqrt(jnp.sum(jnp.square(v))) for k, v in tree.items()}


def one_ranks_share(w, tp: int, axis: int = 0):
    """A row-parallel matrix as ONE tensor-parallel rank multiplies by it
    where the exchange is left out: its own 1/tp of the rows, the rest
    nought (the partial product that was never summed with the others)."""
    own = jnp.arange(w.shape[axis]) < w.shape[axis] // tp
    shape = [1] * w.ndim
    shape[axis] = -1
    return jnp.where(own.reshape(shape), w, 0.0)


def train_reference(cfg: dict, seed: int, texts: np.ndarray,
                    precision: str = "float32", fault: str | None = None,
                    devices=None) -> dict:
    """Follow the first len(texts) optimizer steps in the reference.
    `texts` (steps, 1, rows, seq + 1). Returns losses, per-leaf norms of
    the first clipped gradient, and per-leaf norms of the parameters'
    change after the last step; leaves are keyed `name` -> array with
    one entry per block (one entry for a global)."""
    use = cfg["train"]
    fam = families.find(cfg)
    L = use["num_hidden_layers"]
    matmul = MATMULS[precision]
    words = weights.seed_words(seed)
    kinds = [fam.layer_kind(cfg, i) for i in range(L)]
    mk_layer, mk_glob = _makers(cfg, L, devices)
    params = {"layers": [mk_layer[kinds[i]](words, jnp.int32(i))
                         for i in range(L)],
              "globals": mk_glob(words)}
    # Adam's moments lie where the parameters lie: tied to no sharding,
    # the moments of a model spread over four chips are replicated
    zeros = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p),
                    out_shardings=jax.tree.map(lambda x: x.sharding, params))
    m, v = zeros(params), zeros(params)
    tp = use["tensor_parallel"]

    def loss(p, t, l):
        if fault == "no_exchange":
            # each block's own row-parallel leaves (its kind's)
            p = dict(p, layers=[
                dict(w, **{name: one_ranks_share(w[name], tp, axis)
                           for name, axis in fam.ROW_PARALLEL.items()
                           if name in w})
                for w in p["layers"]])
        return fam.reference.mean_loss(p, t, l, cfg, matmul)

    lg = jax.jit(jax.value_and_grad(loss))

    def update(p, g, m, v, step):
        g, _ = ref.clip_by_global_norm(g, use["clip_grad"])
        norms = {"layers": [_leaf_norms(x) for x in g["layers"]],
                 "globals": _leaf_norms(g["globals"])}
        p, m, v = ref.adamw_step(p, g, m, v, step, use)
        return p, m, v, norms

    update = jax.jit(update, donate_argnums=(0, 2, 3))
    losses, grad_norms = [], None
    for s in range(texts.shape[0]):
        text = texts[s, 0]
        if fault == "half_batch":
            half = text.shape[0] // 2
            text = np.concatenate([text[:half], text[:half]], axis=0)
        loss, grads = lg(params, jnp.asarray(text[:, :-1]),
                         jnp.asarray(text[:, 1:]))
        losses.append(float(loss))
        params, m, v, norms = update(params, grads, m, v,
                                     jnp.float32(s + 1))
        del grads
        if s == 0:
            grad_norms = jax.device_get(norms)

    def change(now, w, i=None, kind=None):
        p0 = mk_glob(w) if i is None else mk_layer[kind](w, i)
        return _leaf_norms({k: now[k] - p0[k] for k in now})

    change = jax.jit(change, static_argnames="kind")  # one program a kind
    ch_layers = [jax.device_get(change(params["layers"][i], words,
                                       jnp.int32(i), kind=kinds[i]))
                 for i in range(L)]
    ch_glob = jax.device_get(change(params["globals"], words))
    for leaf in jax.tree.leaves((params, m, v)):
        leaf.delete()
    return {"loss": losses,
            "grad_norms": _stack(grad_norms["layers"],
                                 grad_norms["globals"]),
            "change_norms": _stack(ch_layers, ch_glob)}


def _makers(cfg, layers, devices=None):
    """({kind: make one block of it}, make the globals), each a compiled
    program of the seed's words (an argument: one program serves every
    seed). On several chips the reference's weights are spread over
    their memory (the 40B's do not fit one): each matrix split along its
    longer axis. The arithmetic stays the plain reference's; the
    compiler places it."""
    lsh, gsh = (lambda like: None), None
    if devices is not None and len(devices) > 1:
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        mesh = Mesh(np.asarray(devices), ("d",))

        def spec(x):
            if x.ndim < 2:
                return NamedSharding(mesh, P())
            ax = int(np.argmax(x.shape))
            return NamedSharding(mesh, P(*["d" if i == ax else None
                                           for i in range(x.ndim)]))

        def lsh(like):  # a kind's shardings, from that kind's own shapes
            return jax.tree.map(spec, jax.eval_shape(
                lambda: weights.make_layer(cfg, 0, like)))

        gsh = jax.tree.map(spec, jax.eval_shape(
            lambda: weights.make_globals(cfg, 0)))
    mk_layer = weights.layer_makers(cfg, layers, shardings=lsh)
    mk_glob = jax.jit(lambda w: weights.make_globals(cfg, w),
                      out_shardings=gsh)
    return mk_layer, mk_glob


def _stack(layers: list, glob: dict) -> dict:
    """Per-block norms -> {leaf name: one entry for each block that has
    a leaf of that name, in layer order}; one entry for a global."""
    names = dict.fromkeys(k for l in layers for k in l)
    out = {k: np.asarray([float(l[k]) for l in layers if k in l])
           for k in names}
    out.update({k: np.asarray([float(v)]) for k, v in glob.items()})
    return out


def _flat(norms: dict) -> np.ndarray:
    return np.concatenate([np.atleast_1d(norms[k]) for k in sorted(norms)])


def worst_leaf_gap(got: dict, want: dict, keep=None) -> float:
    """Largest |got - want| over leaves, each against the reference's norm
    of that leaf or of the median leaf, whichever is larger."""
    g, w = _flat(got), _flat(want)
    floor = float(np.median(w))
    gap = np.abs(g - w) / np.maximum(w, floor)
    if keep is not None:
        gap = gap[keep]
    return float(np.max(gap))


def train_numbers(program: dict, reference: dict) -> dict:
    """The numbers compared for a training cell. `program` and
    `reference` both hold loss, grad_norms, change_norms."""
    out = {}
    for i, (lp, lr) in enumerate(zip(program["loss"], reference["loss"])):
        out[f"loss_gap_step{i + 1}"] = abs(lp - lr) / abs(lr)
    out["grad_norm_gap"] = worst_leaf_gap(program["grad_norms"],
                                          reference["grad_norms"])
    # leaves whose reference gradient is nought to rounding move under
    # Adam by round-off alone: left out by a rule on the gradient
    g = _flat(reference["grad_norms"])
    keep = g >= 1e-3 * float(np.median(g))
    out["change_norm_gap"] = worst_leaf_gap(program["change_norms"],
                                            reference["change_norms"], keep)
    return out


# --------------------------------------------------------------- serving


def serve_reference_logits(cfg: dict, seed: int, samples: list,
                           precision: str = "float32") -> list:
    """For each sample {"tokens": prompt + served, "prompt_len": p}, the
    reference's logits at the positions that predicted the served tokens:
    array (served, vocab). Weights are the seeded ones rounded to the
    served type; blocks are made, used on every sample, and dropped, one
    at a time."""
    use = cfg["serve"]
    fam = families.find(cfg)
    L = use["num_hidden_layers"]
    matmul = MATMULS[precision]
    dt = jnp.bfloat16 if use["weights_dtype"] == "bfloat16" else jnp.float32
    rnd = lambda t: jax.tree.map(  # noqa: E731
        lambda x: x.astype(dt).astype(jnp.float32), t)
    words = weights.seed_words(seed)
    mk_layer = weights.layer_makers(cfg, L, finish=rnd)
    glob = jax.jit(lambda w: rnd(weights.make_globals(cfg, w)))(words)
    T = max(len(s["tokens"]) for s in samples)
    T = -(-T // 256) * 256
    positions = jnp.arange(T)
    programs = {}  # one compiled block per KIND of layer, not per layer

    def blk(w, x, i):
        kind = fam.layer_kind(cfg, i)
        if kind not in programs:
            programs[kind] = jax.jit(lambda w, x: fam.reference.block(
                w, x, cfg, positions, matmul, layer=i))
        return programs[kind](w, x)

    hs = []
    for s in samples:
        toks = np.zeros(T, np.int32)
        toks[:len(s["tokens"])] = s["tokens"]
        hs.append(fam.reference.embed(glob, jnp.asarray(toks)))
    for i in range(L):
        w = mk_layer[fam.layer_kind(cfg, i)](words, jnp.int32(i))
        hs = [blk(w, h, i) for h in hs]
        for leaf in jax.tree.leaves(w):
            leaf.delete()
    # the globals go in as an argument: closed over, the 1.2 GB embedding
    # would be a constant for the compiler to fold
    head = jax.jit(lambda g, x: fam.reference.final_logits(g, x, cfg, matmul))
    out = []
    for s, h in zip(samples, hs):
        p, n = s["prompt_len"], len(s["tokens"])
        out.append(np.asarray(head(glob, h[p - 1:n - 1])))
    return out


def serve_numbers(samples: list, ref_logits: list,
                  put_first: list | None = None) -> dict:
    """`logit_gap`: the widest gap, over every served token of the
    sample, between the reference's best logit and its logit of the
    served token. With `put_first` (the control's logits) the token
    judged is the one the control puts first."""
    widest, n = 0.0, 0
    for k, (s, lg) in enumerate(zip(samples, ref_logits)):
        if put_first is None:
            tok = np.asarray(s["tokens"][s["prompt_len"]:], np.int64)
        else:
            tok = np.argmax(put_first[k], axis=-1)
        got = np.take_along_axis(lg, tok[:, None], axis=-1)[:, 0]
        widest = max(widest, float(np.max(lg.max(axis=-1) - got)))
        n += len(tok)
    return {"logit_gap": widest, "tokens_compared": n}


# ---------------------------------------------------------------- verdict


def verdict(numbers: dict, limits: dict):
    """(correct, compared): every number that has a limit is held to it;
    a number without a limit is reported and not judged; a limit whose
    number is missing fails."""
    compared, ok = {}, True
    for name, limit in limits.items():
        value = numbers.get(name)
        good = value is not None and np.isfinite(value) and value <= limit
        ok = ok and bool(good)
        compared[name] = {"value": value, "limit": limit}
    for name, value in numbers.items():
        compared.setdefault(name, {"value": value, "limit": None})
    return ok, compared


def print_compared(compared: dict):
    for name, row in compared.items():
        print(f"compared {name} = {row['value']} limit {row['limit']}",
              file=sys.stderr)
    sys.stderr.flush()
