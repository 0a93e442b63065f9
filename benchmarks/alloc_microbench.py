"""Allocator microbenchmark (§III-C fast timescale): closed-form active-set
solve across implementations and fleet sizes.

The paper's allocator reacts to per-event demand in milliseconds on one
node; ``allocate_cluster`` vmaps the solve across the whole fleet in one
jitted call ([N, S] batched).  Times are host wall-clock on whatever
backend JAX runs on.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.allocator import allocate_cluster
from repro.core.allocator_np import allocate_cluster_np


def bench(fn, *args, iters: int = 20) -> float:
    fn(*args)                                  # warmup / compile
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out) if hasattr(out, "block_until_ready") else None
    return (time.perf_counter() - t0) / iters * 1e6   # µs


def main() -> None:
    rng = np.random.default_rng(0)
    for N, S in [(6, 18), (64, 32), (1024, 64)]:
        psi = rng.uniform(0, 1e14, (N, S))
        omega = rng.uniform(0, 100, (N, S))
        floors = np.where(rng.random((N, S)) < 0.3,
                          rng.uniform(0, 2e13, (N, S)), 0.0)
        mask = rng.random((N, S)) < 0.9
        cap_g = rng.uniform(5e13, 2e14, N)
        cap_c = rng.uniform(16, 128, N)

        us_np = bench(lambda: allocate_cluster_np(
            psi, psi * 1e-14, omega, floors, floors * 0, cap_g, cap_c, mask))

        j = [jnp.asarray(x) for x in
             (psi, psi * 1e-14, omega, floors, floors * 0, cap_g, cap_c)]
        jm = jnp.asarray(mask)
        f = jax.jit(lambda *a: allocate_cluster(*a))
        us_jax = bench(lambda: jax.block_until_ready(
            f(*j, jm)[0].alloc))

        print(f"alloc,numpy[N={N},S={S}],us_per_call={us_np:.1f},"
              f"per_node_us={us_np / N:.2f}")
        print(f"alloc,jax-vmap[N={N},S={S}],us_per_call={us_jax:.1f},"
              f"per_node_us={us_jax / N:.2f}", flush=True)


if __name__ == "__main__":
    main()
