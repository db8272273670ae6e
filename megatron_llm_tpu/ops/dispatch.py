"""Kernel-or-reference dispatch shared by the Pallas entry points in ops/.

Every entry point takes `use_pallas` (None = "on a TPU backend") and runs
a static gate that may still say no (no block that divides the shape,
a paged call whose K/V heads fill no 128-lane tile — Falcon-7B's one
head of 64 — ...), in which case the numerically matching XLA
reference serves the call. Off the TPU that is the normal path (the CPU
suite relies on it) and nothing is recorded. On a TPU backend a call that
asked for a kernel and did not get one is never silent: it is logged once
per (kernel, shape, gate) and counted here; the trainer's run-facts line,
the engine's counters and `chip_smoke.py` read `fallbacks()`, and
`kernels()` is the positive record of what did reach Mosaic. A cache
shorter than the config's `decode_attn_min_cache` is routed to XLA by the
config itself and is not a fallback. `parallel/mesh.shard_kernel` reports
here too when it has to leave a mesh axis out of a kernel's split (every
shard along it then repeats the work).
"""

from __future__ import annotations

import logging
import threading
from typing import Optional

import jax

_logger = logging.getLogger(__name__)
_LOCK = threading.Lock()
_FALLBACKS: dict = {}  # "kernel[shape] gate=..." -> calls traced
_KERNELS: dict = {}  # kernel -> calls traced into a compiled kernel


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def want_kernel(use_pallas: Optional[bool], interpret: bool = False) -> bool:
    """Whether this call should try its Pallas kernel. `interpret=True`
    is the CPU test path (the real kernel under the Pallas interpreter);
    on a TPU backend it would quietly replace the compiled kernel with
    host-speed emulation, so it raises there."""
    if interpret and on_tpu():
        raise ValueError(
            "interpret=True on a TPU backend: the Pallas interpreter is "
            "the CPU test path; drop the flag (ModelConfig."
            "decode_attn_interpret / interpret=) to run the compiled "
            "kernel")
    return on_tpu() if use_pallas is None else bool(use_pallas)


def report_fallback(kernel: str, gate: str, **shape) -> None:
    """Record that a requested kernel gave way to its XLA reference (or,
    from `shard_kernel`, that its shards repeat each other's work).
    Called at trace time from the dispatch sites; a no-op off the TPU."""
    if not on_tpu():
        return
    dims = ", ".join(f"{k}={v}" for k, v in shape.items())
    key = f"{kernel}[{dims}] gate={gate}"
    with _LOCK:
        seen = _FALLBACKS.get(key, 0)
        _FALLBACKS[key] = seen + 1
    if not seen:
        _logger.warning("requested Pallas kernel gave way: %s", key)


def note_kernel(kernel: str) -> None:
    """Record that a call was traced into its compiled Pallas kernel
    (not counted under the interpreter: that is a CPU test path)."""
    if on_tpu():
        with _LOCK:
            _KERNELS[kernel] = _KERNELS.get(kernel, 0) + 1


def kernels() -> dict:
    """{kernel: traced calls that reached Mosaic} since process start."""
    with _LOCK:
        return dict(_KERNELS)


def fallbacks() -> dict:
    """{"kernel[shape] gate=...": traced calls} since process start."""
    with _LOCK:
        return dict(_FALLBACKS)
