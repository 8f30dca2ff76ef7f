"""jit'd dispatch wrappers over the Pallas kernels.

The kernels run compiled on every backend but the CPU, where they run in
interpret mode, which executes the kernel body in Python for correctness
validation. ``models/`` calls these through ``use_kernel``
flags; the default model path uses the XLA twins (models.flash etc.), which
lower everywhere.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import decode_attention as _dec
from repro.kernels import flash_attention as _fa
from repro.kernels import rmsnorm as _rn
from repro.kernels import ssd_scan as _ssd


def interpret_kernels() -> bool:
    """Interpret mode only on the CPU backend: an accelerator always gets
    the compiled kernel (a refused kernel fails there, never falls back)."""
    return jax.default_backend() == "cpu"


def flash_attention(q, k, v, mask=None, *, causal: bool = True,
                    window: int = 0):
    """Drop-in for models.layers.sdpa's kernel path (mask arg accepted for
    signature compatibility; masking is structural)."""
    return _fa.flash_attention_fwd(q, k, v, causal=causal, window=window,
                                   interpret=interpret_kernels())


def decode_attention(q, k_cache, v_cache, pos, position, *, window: int = 0):
    return _dec.decode_attention(q, k_cache, v_cache, pos, position,
                                 window=window, interpret=interpret_kernels())


def ssd_scan(x, a, b, c, chunk: int, initial_state=None):
    if initial_state is not None:
        raise NotImplementedError(
            "kernel path supports zero initial state (prefill); chunked "
            "continuation uses the XLA path")
    return _ssd.ssd_scan(x, a, b, c, chunk=chunk, interpret=interpret_kernels())


def rmsnorm(x, scale, eps: float = 1e-5):
    return _rn.rmsnorm(x, scale, eps=eps, interpret=interpret_kernels())
