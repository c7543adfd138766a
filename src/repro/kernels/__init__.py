"""Pallas TPU kernels for the compute hot spots.

Each kernel ships three modules:
  kernel.py — ``pl.pallas_call`` body with explicit BlockSpec VMEM tiling
  ops.py    — jit'd public wrapper (layout handling, defaults, platform pick)
  ref.py    — pure-jnp oracle used by the allclose test sweeps

The ops choose by platform when ``interpret`` is left at ``None``: on a
TPU the Pallas kernel is always compiled; elsewhere the aggregation ops
run their ``ref.py`` oracle and the model kernels run in interpret mode.
``interpret=True`` is for tests: it runs the kernel body through the
Pallas interpreter on any backend.
"""
import jax

# The combines sum pairwise-masked f32 rows whose masks cancel only in
# full f32. The TPU's default f32 matmul precision rounds operands to
# bf16, so every combine and its oracle ask for the f32 contraction.
EXACT_F32 = jax.lax.Precision.HIGHEST


def on_tpu() -> bool:
    """True when the default backend is a TPU — the only platform on
    which the ops compile their Pallas kernels."""
    return jax.default_backend() == "tpu"
