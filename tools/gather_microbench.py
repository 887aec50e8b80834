"""Microbenchmark TPU lowering of per-row small-table gathers vs rewrites.

The grower's partition step does several [K]- or [L]-table lookups indexed
by a [n] row vector.  XLA's TPU gather for this pattern can serialize; the
candidates below measure the alternatives used to pick the grower's
formulation:

  gather      x[idx] as written
  select      K-way where-select chain
  onehot_dot  one-hot [n, K] @ table [K] contraction
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def timeit(fn, *args, iters=30):
    jax.block_until_ready(fn(*args))  # compile
    t0 = time.time()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.time() - t0) / iters * 1e3


def main():
    n = 1 << 20
    rng = np.random.default_rng(0)

    for T in (25, 256):
        table = jnp.asarray(rng.normal(size=T).astype(np.float32))
        idx = jnp.asarray(rng.integers(0, T, size=n), dtype=jnp.int32)

        g = jax.jit(lambda t, i: t[i])
        ms = timeit(g, table, idx)
        print(f"T={T:4d} gather      : {ms:8.3f} ms", flush=True)

        def sel(t, i):
            acc = jnp.zeros(n, jnp.float32)
            for k in range(T):
                acc = jnp.where(i == k, t[k], acc)
            return acc
        if T <= 32:
            ms = timeit(jax.jit(sel), table, idx)
            print(f"T={T:4d} select      : {ms:8.3f} ms", flush=True)

        def ohdot(t, i):
            oh = (i[:, None] == jnp.arange(T)).astype(jnp.bfloat16)
            return oh @ t.astype(jnp.bfloat16)
        ms = timeit(jax.jit(ohdot), table, idx)
        print(f"T={T:4d} onehot_dot  : {ms:8.3f} ms", flush=True)

    # take_along_axis pattern: bins_t [F, n], per-row feature index
    F = 28
    bins_t = jnp.asarray(rng.integers(0, 256, size=(F, n)), dtype=jnp.int32)
    f_r = jnp.asarray(rng.integers(0, F, size=n), dtype=jnp.int32)

    taa = jax.jit(lambda b, f: jnp.take_along_axis(b, f[None, :], axis=0)[0])
    ms = timeit(taa, bins_t, f_r)
    print(f"taa [F={F},n] gather   : {ms:8.3f} ms", flush=True)

    K = 25
    sel_feat = jnp.asarray(rng.integers(0, F, size=K), dtype=jnp.int32)
    kk_r = jnp.asarray(rng.integers(0, K, size=n), dtype=jnp.int32)

    def rows_then_select(b, sf, kk):
        rows = b[sf]                     # [K, n] contiguous row gather
        acc = jnp.zeros(n, jnp.int32)
        for k in range(K):
            acc = jnp.where(kk == k, rows[k], acc)
        return acc
    ms = timeit(jax.jit(rows_then_select), bins_t, sel_feat, kk_r)
    print(f"rows[K]+select chain   : {ms:8.3f} ms", flush=True)


if __name__ == "__main__":
    main()
