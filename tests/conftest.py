"""Test harness: run everything on a virtual 8-device CPU mesh.

The reference cannot test collectives without >=2 real GPUs
(SURVEY.md §4); on JAX we force 8 host-platform devices so TP/PP/DP tests
run anywhere. Must set env vars before jax initializes.
"""

import importlib.util
import os

# Load the shared provisioning helper WITHOUT importing the package (the
# package __init__ imports jax; env must be set before jax loads).
_repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "_virtual_mesh",
    os.path.join(_repo, "megatron_llm_tpu", "utils", "virtual_mesh.py"),
)
_vm = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_vm)
_vm.force_virtual_cpu_devices(8)

# NOTE: the persistent compilation cache stays OFF for the suite (the
# entry points switch it on for themselves, utils/compile_cache.py).
# Re-checked on jaxlib 0.9.0 (PR 21): the old heap corruption did not
# reproduce (two passes of its reproducer, tests/test_disagg_serving.py
# + test_router.py, against a warm cache), but there is nothing to gain
# — those passes took 28 s cold and 27 s warm, the tiny engines' compiles
# mostly sit under the cache's 1 s persistence threshold — and XLA:CPU's
# loader warns on every hit that the cached executable was built for
# machine features the host does not report ("could lead to SIGILL").

import jax  # noqa: E402

jax.config.update("jax_default_matmul_precision", "highest")

assert len(jax.devices()) == 8, (
    f"expected 8 virtual CPU devices, got {jax.devices()}"
)

import pytest  # noqa: E402

# ---------------------------------------------------------------------------
# Kernel interpret-mode policy — THE one switch for every Pallas suite
# (flash, rmsnorm, ring, decode, paged decode, ragged prefill). Off-TPU
# the real kernels run through the Pallas interpreter so CPU tier-1
# exercises every kernel; on TPU they compile for real. Override with
# MEGATRON_TPU_KERNEL_INTERPRET=0/1 (e.g. =1 on TPU to debug a kernel
# through the interpreter, =0 to skip kernel suites' interpreted runs).
# ---------------------------------------------------------------------------


def kernel_interpret_mode() -> bool:
    """True -> pass interpret=True (and decode_attn_interpret=True in
    configs) so the REAL Pallas kernels run under the interpreter; the
    uniform CPU tier-1 path for every kernel suite. Suites read this
    ONCE at module import (`from conftest import kernel_interpret_mode`)
    — one policy, one env var, no per-file hardcoding."""
    env = os.environ.get("MEGATRON_TPU_KERNEL_INTERPRET")
    if env is not None:
        return env.lower() not in ("0", "false", "")
    return jax.default_backend() != "tpu"


@pytest.fixture
def mesh8():
    """2x2x2 (data, stage, model) mesh on 8 CPU devices."""
    from megatron_llm_tpu.parallel import initialize_parallel
    from megatron_llm_tpu.parallel.mesh import destroy_parallel

    ctx = initialize_parallel(dp=2, pp=2, tp=2)
    yield ctx
    destroy_parallel()


@pytest.fixture
def tp8():
    """Pure tensor-parallel mesh tp=8."""
    from megatron_llm_tpu.parallel import initialize_parallel
    from megatron_llm_tpu.parallel.mesh import destroy_parallel

    ctx = initialize_parallel(dp=1, pp=1, tp=8, sequence_parallel=True)
    yield ctx
    destroy_parallel()
