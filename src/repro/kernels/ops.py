"""jit'd public wrappers for the Pallas kernels.

Model-facing shapes in, kernel-native shapes inside.  Off a TPU the
kernels execute in ``interpret=True`` mode — the kernel body runs in
Python for correctness validation; TPU is the performance target.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention_bhsd
from repro.kernels.rmsnorm import rmsnorm_2d
from repro.kernels.ssd_scan import ssd_scan_bhsp


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# --------------------------------------------------------------------------- #
# flash attention
# --------------------------------------------------------------------------- #
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, block_q: int = 128,
                    block_k: int = 128) -> jax.Array:
    """q [B,S,H,d]; k,v [B,S,KV,d] -> [B,S,H,d] (blockwise online softmax)."""
    B, S, H, d = q.shape
    KV = k.shape[2]
    qr = q.transpose(0, 2, 1, 3).reshape(B * H, S, d)
    kr = k.transpose(0, 2, 1, 3).reshape(B * KV, S, d)
    vr = v.transpose(0, 2, 1, 3).reshape(B * KV, S, d)
    # pick block sizes that divide S
    bq = block_q
    while S % bq:
        bq //= 2
    bk = block_k
    while S % bk:
        bk //= 2
    out = flash_attention_bhsd(qr, kr, vr, causal=causal, block_q=max(bq, 1),
                               block_k=max(bk, 1), interpret=_interpret())
    return out.reshape(B, H, S, d).transpose(0, 2, 1, 3)


# --------------------------------------------------------------------------- #
# Mamba2 SSD scan
# --------------------------------------------------------------------------- #
def ssd_scan(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
             C: jax.Array, chunk: int = 256,
             initial_state: Optional[jax.Array] = None
             ) -> Tuple[jax.Array, jax.Array]:
    """x [b,s,h,p]; dt [b,s,h]; A [h]; B,C [b,s,g,n] -> (y, state [b,h,p,n]).

    Group broadcast (g -> h) happens here via gather (no HBM repeat for the
    common g=1 case on TPU: XLA folds the broadcast into the kernel feed).
    """
    b, s, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    rep = h // g
    if initial_state is not None:
        # fold an incoming state by prepending a virtual chunk is not
        # supported; callers pass None in training/prefill (decode uses the
        # O(1) recurrence instead).
        raise NotImplementedError("initial_state handled by decode path")

    xr = x.transpose(0, 2, 1, 3).reshape(b * h, s, p)
    dtr = dt.transpose(0, 2, 1).reshape(b * h, s, 1)
    Ar = jnp.broadcast_to(A[None, :], (b, h)).reshape(b * h, 1)
    Bh = jnp.repeat(B, rep, axis=2).transpose(0, 2, 1, 3).reshape(b * h, s, n)
    Ch = jnp.repeat(C, rep, axis=2).transpose(0, 2, 1, 3).reshape(b * h, s, n)

    while s % chunk:
        chunk //= 2
    y, state = ssd_scan_bhsp(xr, dtr, Ar, Bh, Ch, chunk=max(chunk, 1),
                             interpret=_interpret())
    y = y.reshape(b, h, s, p).transpose(0, 2, 1, 3)
    state = state.reshape(b, h, p, n).astype(x.dtype)
    return y, state


# --------------------------------------------------------------------------- #
# fused RMSNorm
# --------------------------------------------------------------------------- #
def rmsnorm(x: jax.Array, weight: jax.Array, eps: float = 1e-5) -> jax.Array:
    """x [..., d]; weight [d]."""
    shape = x.shape
    d = shape[-1]
    rows = int(np_prod(shape[:-1]))
    xr = x.reshape(rows, d)
    block = 128
    while rows % block:
        block //= 2
    out = rmsnorm_2d(xr, weight, eps=eps, block_rows=max(block, 1),
                     interpret=_interpret())
    return out.reshape(shape)


def np_prod(xs) -> int:
    out = 1
    for x in xs:
        out *= int(x)
    return out
