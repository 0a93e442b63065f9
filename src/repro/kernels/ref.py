"""Pure-jnp oracles for every Pallas kernel (the allclose references).

Each oracle is the *mathematically obvious* implementation — where possible
a different algorithm than the kernel (e.g. the SSD oracle is a sequential
recurrence, not the chunked dual form), so the comparison validates the
algorithm as well as the lowering.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp


# --------------------------------------------------------------------------- #
# flash attention (causal GQA)
# --------------------------------------------------------------------------- #
def attention_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                  causal: bool = True) -> jax.Array:
    """q [B,S,H,d]; k,v [B,S,KV,d] -> [B,S,H,d].  fp32 softmax."""
    B, S, H, d = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, S, KV, G, d)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, k).astype(jnp.float32)
    scores = scores / math.sqrt(d)
    if causal:
        mask = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
        scores = jnp.where(mask[None, None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs.astype(v.dtype), v)
    return out.reshape(B, S, H, d)


# --------------------------------------------------------------------------- #
# Mamba2 SSD — sequential state-space recurrence (the "linear" form)
# --------------------------------------------------------------------------- #
def ssd_scan_sequential_ref(x: jax.Array, dt: jax.Array, A: jax.Array,
                            B: jax.Array, C: jax.Array,
                            initial_state: Optional[jax.Array] = None
                            ) -> Tuple[jax.Array, jax.Array]:
    """x [b,s,h,p]; dt [b,s,h]; A [h]; B,C [b,s,g,n] -> (y, final_state).

    h_t = h_{t-1} · exp(dt_t A) + dt_t · x_t ⊗ B_t ;  y_t = h_t · C_t.
    Sequential over s — the oracle for the chunked/dual implementations.
    """
    b, s, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    rep = h // g
    Bh = jnp.repeat(B, rep, axis=2).astype(jnp.float32)     # [b,s,h,n]
    Ch = jnp.repeat(C, rep, axis=2).astype(jnp.float32)
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    Af = A.astype(jnp.float32)

    state0 = (jnp.zeros((b, h, p, n), jnp.float32) if initial_state is None
              else initial_state.astype(jnp.float32))

    def step(state, inp):
        xt, dtt, Bt, Ct = inp                     # [b,h,p],[b,h],[b,h,n]x2
        decay = jnp.exp(dtt * Af[None, :])        # [b,h]
        state = state * decay[:, :, None, None] + jnp.einsum(
            "bh,bhp,bhn->bhpn", dtt, xt, Bt)
        y = jnp.einsum("bhpn,bhn->bhp", state, Ct)
        return state, y

    xs = (jnp.moveaxis(xf, 1, 0), jnp.moveaxis(dtf, 1, 0),
          jnp.moveaxis(Bh, 1, 0), jnp.moveaxis(Ch, 1, 0))
    final, ys = jax.lax.scan(step, state0, xs)
    y = jnp.moveaxis(ys, 0, 1).astype(x.dtype)
    return y, final.astype(x.dtype)


# --------------------------------------------------------------------------- #
# fused RMS norm
# --------------------------------------------------------------------------- #
def rmsnorm_ref(x: jax.Array, weight: jax.Array,
                eps: float = 1e-5) -> jax.Array:
    """x [..., d]; weight [d] — fp32 statistics, cast back to x.dtype."""
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * weight.astype(jnp.float32)).astype(x.dtype)
