"""Pallas TPU kernels and the one platform check that selects them.

Every kernel here is dispatched behind an XLA-path reference that
defines its semantics; off the TPU a kernel runs only in interpret mode
(the parity-test surface, never the throughput path)."""
from __future__ import annotations

import jax


def on_tpu() -> bool:
    """True when JAX's default backend is a TPU: the kernels then
    compile natively; everywhere else they run interpreted."""
    return jax.default_backend() == "tpu"
