"""Device-mesh topology — the TPU-native replacement for the reference's
process-group "mpu" layer (ref: megatron/core/parallel_state.py:51-524).

Where the reference builds NCCL process groups per (tp, pp, dp) coordinate
and offers ~40 rank/size getters, on TPU a single `jax.sharding.Mesh` with
named axes ("data", "stage", "model") carries the whole topology: TP/SP is
sharding over "model", PP over "stage", DP over "data". XLA's GSPMD inserts
the collectives the reference issues by hand.

The rank-order convention matches the reference so multi-host layouts map
the same way: tp is innermost (fastest-varying), then pp, then dp
(ref: parallel_state.py:88-130 builds dp groups with stride tp*pp).
"""

from __future__ import annotations

import contextlib
from dataclasses import dataclass
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"
STAGE_AXIS = "stage"
CONTEXT_AXIS = "context"
MODEL_AXIS = "model"
AXIS_NAMES = (DATA_AXIS, STAGE_AXIS, CONTEXT_AXIS, MODEL_AXIS)

_CONTEXT: Optional["ParallelContext"] = None

# Thread-local context OVERRIDE (ISSUE 14): `use_mesh` scopes are
# per-thread, so N tp-serving engines' serve threads can each trace
# under their OWN mesh concurrently — a process-global swap would make
# one replica bake another's mesh into its constraints (or force a
# fleet-serializing lock around every dispatch). Reads fall back to
# the installed global (`initialize_parallel`), which trainers and
# tests keep using unchanged.
import threading as _threading

_TLS = _threading.local()


def _effective_context() -> Optional["ParallelContext"]:
    return getattr(_TLS, "ctx", None) or _CONTEXT


def maybe_initialize_distributed() -> int:
    """Multi-host bring-up — the analogue of the reference's
    torch.distributed.init_process_group + NCCL rendezvous
    (ref: initialize.py:180-217).

    On TPU pods the runtime publishes coordinator/task env vars and
    `jax.distributed.initialize()` needs no arguments; after it returns,
    `jax.devices()` spans every host and the (data, stage, model) mesh
    built below automatically lays DCN-crossing axes outermost. No-op on
    single-process runs. Returns the process count.

    MUST run before ANY other jax call (jax.devices()/process_count()
    initialize the local-only backend and make the rendezvous impossible)
    — every entry point calls this first, before args_to_configs touches
    jax.devices(). Real rendezvous failures propagate; only
    double-initialization is tolerated.
    """
    import os

    multiproc_env = any(
        v in os.environ
        for v in ("JAX_COORDINATOR_ADDRESS", "COORDINATOR_ADDRESS",
                  "MEGASCALE_COORDINATOR_ADDRESS")
    )
    # GCE/GKE TPU pods set none of the coordinator vars — jax auto-detects
    # the cluster from TPU metadata. Detect the multi-host pod from the
    # worker-hostnames metadata env var the TPU runtime publishes.
    hostnames = os.environ.get("TPU_WORKER_HOSTNAMES", "")
    if len([h for h in hostnames.split(",") if h]) > 1:
        multiproc_env = True
    if multiproc_env:
        try:
            jax.distributed.initialize()
        except RuntimeError as e:
            if "already" not in str(e):
                raise
    return jax.process_count()


def build_mesh(
    dp: int = 1,
    pp: int = 1,
    tp: int = 1,
    devices: Optional[Sequence[jax.Device]] = None,
    cp: int = 1,
) -> Mesh:
    """Build the (data, stage, context, model) mesh.

    Axis order puts `model` innermost so TP collectives ride the
    fastest ICI links (analogue of the reference keeping TP within a node,
    ref: docs/guide/faq.md policy "TP <= GPUs/node"); `context` sits just
    outside so the ring-attention ppermute hops are next-nearest.
    """
    if devices is None:
        devices = jax.devices()
    n = dp * pp * cp * tp
    if len(devices) < n:
        raise ValueError(
            f"need {n} devices for dp={dp} pp={pp} cp={cp} tp={tp}, "
            f"have {len(devices)}"
        )
    dev_array = np.asarray(devices[:n]).reshape(dp, pp, cp, tp)
    return Mesh(dev_array, AXIS_NAMES)


@dataclass
class ParallelContext:
    """Holds the mesh + parallel flags; the analogue of the reference's
    module-global parallel state (ref: parallel_state.py:20-49)."""

    mesh: Mesh
    sequence_parallel: bool = False

    # -- size getters (ref: parallel_state.py:327-372) --------------------
    @property
    def dp(self) -> int:
        return self.mesh.shape[DATA_AXIS]

    @property
    def pp(self) -> int:
        return self.mesh.shape[STAGE_AXIS]

    @property
    def cp(self) -> int:
        return self.mesh.shape[CONTEXT_AXIS]

    @property
    def tp(self) -> int:
        return self.mesh.shape[MODEL_AXIS]

    @property
    def world_size(self) -> int:
        return self.dp * self.pp * self.cp * self.tp

    def sharding(self, *spec) -> NamedSharding:
        return NamedSharding(self.mesh, P(*spec))


def initialize_parallel(
    dp: int = 1, pp: int = 1, tp: int = 1, sequence_parallel: bool = False,
    devices: Optional[Sequence[jax.Device]] = None, cp: int = 1,
) -> ParallelContext:
    """Create and install the global context (ref analogue:
    initialize_model_parallel, parallel_state.py:51)."""
    global _CONTEXT
    mesh = build_mesh(dp, pp, tp, devices, cp=cp)
    _CONTEXT = ParallelContext(mesh=mesh, sequence_parallel=sequence_parallel)
    return _CONTEXT


def get_context() -> Optional[ParallelContext]:
    return _effective_context()


def destroy_parallel() -> None:
    """Ref analogue: destroy_model_parallel (parallel_state.py:497)."""
    global _CONTEXT
    _CONTEXT = None


@contextlib.contextmanager
def use_mesh(ctx: ParallelContext):
    """Temporarily install a context for THIS thread (tests use this
    to swap meshes; tp serving engines scope every dispatch with it).
    Thread-local by design — see _effective_context."""
    prev = getattr(_TLS, "ctx", None)
    _TLS.ctx = ctx
    try:
        yield ctx
    finally:
        _TLS.ctx = prev


# ---------------------------------------------------------------------------
# Pallas kernels under a mesh
# ---------------------------------------------------------------------------


def _spec_axes(entry) -> tuple:
    if entry is None:
        return ()
    return entry if isinstance(entry, tuple) else (entry,)


def shard_kernel(fn, in_specs, out_specs, check_vma=True):
    """`fn` (a Pallas kernel call) run once per shard of the installed
    mesh. Mosaic kernels cannot be partitioned by GSPMD ("wrap the call
    in a shard_map"), and their lowering insists on EVERY axis of a
    multi-device mesh being manual, size-1 axes included. So under such
    a mesh the call sits in a shard_map over all the axes an enclosing
    shard_map has not already made manual: operands split as `in_specs`
    say (batch over `data`, groups/heads over `model`, ...) and are
    replicated over the axes the specs do not name. An axis whose size
    does not divide every dim assigned to it is dropped from the specs:
    each shard along it computes the whole dim — correct, axis-size-fold
    redundant (MQA's one group under tp), and reported next to the kernel
    fallbacks (ops/dispatch.py). Spec entries naming an already-manual
    axis are vacuous. With no mesh, one device, or nothing left to make
    manual, `fn` is called as is.

    `check_vma=False` is for kernels with scalar-prefetch operands (the
    paged and dense decode kernels): the Pallas interpreter evaluates
    their index maps against operands whose varying-axes types it never
    saw at trace time, which the checker rejects. Kernels that are
    differentiated (flash) need the default: a custom_vjp's cotangents
    must carry the primals' varying-axes types."""
    def call(*args):
        ctx = _effective_context()
        if ctx is None or ctx.mesh.size == 1:
            return fn(*args)
        mesh = ctx.mesh
        outer = set(jax.sharding.get_abstract_mesh().manual_axes)
        axes = set(mesh.axis_names) - outer
        if not axes:
            return fn(*args)
        keep = set(axes)
        for spec, x in zip(in_specs, args):
            for entry, n in zip(spec, x.shape):
                size = 1
                for a in _spec_axes(entry):
                    size *= mesh.shape[a] if a in axes else 1
                if n % size:
                    keep -= set(_spec_axes(entry))
        idle = sorted(a for a in axes - keep if mesh.shape[a] > 1)
        if idle:
            from megatron_llm_tpu.ops.dispatch import report_fallback

            report_fallback(
                getattr(fn, "func", fn).__name__, "shard_kernel",
                repeated_over=",".join(idle),
                shapes=" ".join("x".join(map(str, x.shape)) for x in args))

        def strip(spec):
            parts = []
            for entry in spec:
                names = tuple(a for a in _spec_axes(entry) if a in keep)
                parts.append(names if len(names) > 1
                             else names[0] if names else None)
            return P(*parts)

        # nested in another shard_map the mesh comes from its context
        # (whose axis types already say which axes are manual), and the
        # call is remat'd: jax 0.9.0 mis-names a nested region's
        # residuals under AD, so its only residuals must be its inputs —
        # one extra kernel forward in the backward (KNOWN_FAILURES.md
        # "Nested shard_map + AD"; owner: whoever next raises the jax
        # pin — delete `jax.checkpoint` here when
        # tests/test_tpu_lowering.py's pp2tp2 layout compiles without it)
        return jax.shard_map(
            jax.checkpoint(fn) if outer else fn,
            mesh=None if outer else mesh,
            in_specs=tuple(strip(s) for s in in_specs),
            out_specs=jax.tree.map(strip, out_specs,
                                   is_leaf=lambda x: isinstance(x, P)),
            axis_names=axes, check_vma=check_vma,
        )(*args)

    return call


# ---------------------------------------------------------------------------
# Activation sharding constraints
# ---------------------------------------------------------------------------
# Model code calls `shard_activation(x, kind)` at the few load-bearing points;
# when no mesh is installed these are no-ops, so single-device code paths are
# identical. GSPMD propagates everything else.

# The sequence dim is ALWAYS sharded over `context` (a size-1 no-op unless
# context parallelism is on — ring attention handles the one op that mixes
# sequence positions). Under sequence parallelism the norm/dropout regions
# ("hidden_seq") shard seq over `model` TOO: GSPMD then materialises the
# reference's SP all-gather-before-column-parallel / reduce-scatter-after-
# row-parallel pattern (ref: mappings.py:191-246, layers.py:225-296) from
# the transition between "hidden_seq" and the matmul-region specs below,
# and every saved residual/norm activation costs 1/tp the memory.
_ACTIVATION_SPECS = {
    # (batch, seq, hidden) residual stream at matmul regions
    "hidden": P(DATA_AXIS, CONTEXT_AXIS, None),
    # (batch, seq, hidden) at layer boundaries / norm+dropout regions —
    # seq additionally sharded over `model` under sequence parallelism
    "hidden_seq": P(DATA_AXIS, (CONTEXT_AXIS, MODEL_AXIS), None),
    # (batch, seq, heads, head_dim) — heads over model axis (TP attention)
    "heads": P(DATA_AXIS, CONTEXT_AXIS, MODEL_AXIS, None),
    # (batch, seq, kv_heads, q_per_kv, head_dim) grouped GQA layout
    "groups": P(DATA_AXIS, CONTEXT_AXIS, MODEL_AXIS, None, None),
    # (batch, seq, ffn) MLP intermediate — ffn over model axis
    "ffn": P(DATA_AXIS, CONTEXT_AXIS, MODEL_AXIS),
    # (batch, seq, 2, ffn) GLU intermediate, gate/up axis unsharded
    "glu_ffn": P(DATA_AXIS, CONTEXT_AXIS, None, MODEL_AXIS),
    # (batch, seq, vocab) logits — vocab-parallel
    # (ref: layers.py:128-210 VocabParallelEmbedding / parallel_lm_logits)
    "logits": P(DATA_AXIS, CONTEXT_AXIS, MODEL_AXIS),
}


_MANUAL_DEPTH = 0
_BARRIER_DEPTH = 0


@contextlib.contextmanager
def manual_region(constraint_barriers: bool = False):
    """Mark a shard_map(manual-axes) body: activation constraints are
    skipped inside and GSPMD propagation from the param shardings covers
    the auto axes of the body instead. (The old build rejected a
    constraint inside a manual region outright; jax 0.9.0 accepts one
    over the auto axes — tests/test_pipeline.py passes with them applied
    — but which program is better on the chip is unmeasured, so the
    behaviour stays. KNOWN_FAILURES.md, "Open".)

    `constraint_barriers=True` (the explicit ZeRO-1 path,
    optimizer/zero1.py): each skipped constraint site emits a
    `lax.optimization_barrier` instead of nothing. A sharding
    constraint is a fusion boundary in the GSPMD program; without a
    stand-in, the manual program fuses elementwise chains differently
    and bf16 intermediates round differently — measured on the CPU
    backend as a per-layer last-ulp forward divergence. The barrier
    reproduces the replicated program's fusion boundaries, which is
    what makes the zero1-vs-replicated BITWISE contract hold in bf16
    (tests/test_zero1.py)."""
    global _MANUAL_DEPTH, _BARRIER_DEPTH
    _MANUAL_DEPTH += 1
    _BARRIER_DEPTH += 1 if constraint_barriers else 0
    try:
        yield
    finally:
        _MANUAL_DEPTH -= 1
        _BARRIER_DEPTH -= 1 if constraint_barriers else 0


def in_manual_region() -> bool:
    return _MANUAL_DEPTH > 0


@jax.custom_vjp
def _fusion_barrier(x):
    return jax.lax.optimization_barrier(x)


def _fusion_barrier_fwd(x):
    return jax.lax.optimization_barrier(x), None


def _fusion_barrier_bwd(_, ct):
    # with_sharding_constraint transposes to a constraint on the
    # cotangent — the replicated program's BACKWARD has the same fusion
    # boundaries, so the stand-in must too
    return (jax.lax.optimization_barrier(ct),)


_fusion_barrier.defvjp(_fusion_barrier_fwd, _fusion_barrier_bwd)


def shard_activation(x, kind: str):
    ctx = _effective_context()
    if ctx is None or _MANUAL_DEPTH:
        if ctx is not None and _BARRIER_DEPTH:
            return _fusion_barrier(x)
        return x
    spec = _ACTIVATION_SPECS[kind]
    if kind == "hidden_seq" and not ctx.sequence_parallel:
        spec = _ACTIVATION_SPECS["hidden"]
    return jax.lax.with_sharding_constraint(x, NamedSharding(ctx.mesh, spec))
