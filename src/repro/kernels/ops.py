"""Public jitted wrappers for the Pallas kernels.

On this CPU-only container the wrappers run the kernels in ``interpret=True``
mode (the kernel body executes in Python/XLA-CPU, bit-faithful to the TPU
semantics); on a real TPU backend they compile through Mosaic.  The choice is
automatic, overridable via the ``interpret=`` argument.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.hlsh_attention import hlsh_attention_pallas
from repro.kernels.int4_matmul import int4_matmul_pallas


def default_interpret() -> bool:
    """Interpret-mode default of the kernels below: interpret everywhere
    except on a real TPU backend, where they compile through Mosaic."""
    return jax.default_backend() != "tpu"


_default_interpret = default_interpret


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    causal: bool = False, block_q: int = 128,
                    block_k: int = 128,
                    interpret: bool | None = None) -> jnp.ndarray:
    """Multi-head attention; q: (B, H, Sq, D), k/v: (B, Hkv, Sk, D)."""
    interp = _default_interpret() if interpret is None else interpret
    return flash_attention_pallas(q, k, v, causal=causal, block_q=block_q,
                                  block_k=block_k, interpret=interp)


@functools.partial(jax.jit, static_argnames=("block_q", "block_k",
                                             "interpret"))
def hlsh_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                   keep: jnp.ndarray, share_src: jnp.ndarray,
                   block_q: int = 128, block_k: int = 128,
                   interpret: bool | None = None) -> jnp.ndarray:
    """Full HLSH semantics: masked attention core (Pallas) + share map."""
    interp = _default_interpret() if interpret is None else interpret
    out = hlsh_attention_pallas(q, k, v, keep, block_q=block_q,
                                block_k=block_k, interpret=interp)
    return jnp.take_along_axis(out, share_src[..., None], axis=1)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k",
                                             "interpret"))
def int4_matmul(x: jnp.ndarray, w_packed: jnp.ndarray, scale,
                block_m: int = 128, block_n: int = 128, block_k: int = 128,
                interpret: bool | None = None) -> jnp.ndarray:
    interp = _default_interpret() if interpret is None else interpret
    return int4_matmul_pallas(x, w_packed, scale, block_m=block_m,
                              block_n=block_n, block_k=block_k,
                              interpret=interp)
