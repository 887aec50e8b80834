"""Micro-benchmark the batched histogram contraction in isolation.

Separates kernel time from the rest of the grower round so tuning targets
the right thing: K x block x impl at the Higgs-1M bench shape.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from lightgbm_tpu.ops.histogram import (build_histogram_batched_t,
                                        pack_stats)


def bench_one(n, F, B, K, block, impl, precision="hilo", iters=20):
    rng = np.random.default_rng(0)
    nb = n // block
    bins_t = jnp.asarray(rng.integers(0, B, size=(nb, F, block)),
                         dtype=jnp.int32)
    g = jnp.asarray(rng.normal(size=n).astype(np.float32))
    h = jnp.abs(g) + 0.1
    mask = jnp.ones(n, jnp.float32)
    stats = pack_stats(g, h, mask, precision)
    S = stats.shape[0]
    stats_blocks = stats.reshape(S, nb, block)
    leaf_blocks = jnp.asarray(
        rng.integers(0, 2 * K, size=(nb, block)), dtype=jnp.int32)
    slots = jnp.arange(K, dtype=jnp.int32)

    fn = jax.jit(lambda bt, sb, lb, sl: build_histogram_batched_t(
        bt, sb, lb, sl, B, precision, impl=impl))
    t0 = time.time()
    jax.block_until_ready(fn(bins_t, stats_blocks, leaf_blocks, slots))
    compile_s = time.time() - t0
    t0 = time.time()
    for _ in range(iters):
        out = fn(bins_t, stats_blocks, leaf_blocks, slots)
    jax.block_until_ready(out)
    ms = (time.time() - t0) / iters * 1e3
    flops = 2.0 * n * F * B * K * S
    tflops = flops / (ms / 1e3) / 1e12
    print(f"impl={impl:6s} K={K:2d} S={S} block={block:6d}: {ms:8.2f} ms "
          f"({tflops:6.1f} TFLOP/s eff)  compile {compile_s:5.1f}s",
          flush=True)
    return ms


def main():
    n = 1 << 20
    F, B = 28, 256
    configs = []
    for block in (8192, 16384, 32768, 65536, 131072):
        configs.append((15, block, "xla"))
        configs.append((25, block, "xla"))
    for block in (512, 1024, 2048, 4096):
        configs.append((25, block, "pallas"))
    sel = os.environ.get("ONLY", "")
    for K, block, impl in configs:
        if sel and sel not in impl:
            continue
        try:
            bench_one(n, F, B, K, block, impl)
        except Exception as exc:
            print(f"impl={impl} K={K} block={block}: FAILED "
                  f"{type(exc).__name__}: {str(exc)[:200]}", flush=True)


if __name__ == "__main__":
    main()
