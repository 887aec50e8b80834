"""Time the perfeature histogram kernel alone, at the benchmark cells' shape.

One `pallas2` call per (bins, columns, slots) point over `--rows` rows of
u8 bins in 8192-row blocks, hilo statistics: what `%hist_build*` costs per
call inside the grow program, without the program around it.  The sweep is
the cells' own axes: B in {63, 255}; 32 stored columns of which 28 or all 32
are live (`live_columns`, ops/histogram.py); K in {1, 4, 16, 25} slots, the
ramp's widths and the loop's; and the Criteo shard's shape, 67 live of 96
stored columns at 255 bins over 13 x 2^20 rows, which the kernel runs as
three feature chunks.  Each point says how many columns one dot of the
kernel stacks there (`perfeature_columns_per_dot`).  PERF.md §5 keeps the
last table: the per-slot term it shows is what the next kernel change
starts from.

    chiprun -- python tools/hist_microbench.py [--rows N] [--iters N]

Prints one line per point and the table, and appends the points as JSON
lines to chiprun_out/hist_microbench.jsonl.  No benchmark cell runs this.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from benchmarks.lib.peaks import PEAKS
from lightgbm_tpu.ops.histogram import (build_histogram_batched_t, pack_stats,
                                        perfeature_chunks,
                                        perfeature_columns_per_dot)

BLOCK = 8192
# (bins, stored columns, live columns, rows; None: --rows): the Higgs cells'
# table at both bin counts, every column live beside it, the Criteo shard
SHAPES = ((63, 32, 28, None), (63, 32, 32, None), (255, 32, 28, None),
          (255, 32, 32, None), (255, 96, 67, 13 << 20))
SLOTS = (1, 4, 16, 25)


def operands(rows: int, bins: int, stored: int, live: int):
    """Device-made operands of one call: [nb, stored, 8192] u8 bins whose
    columns past `live` are the learner's padding (constant bin 0)."""
    nb = rows // BLOCK
    kb, kg, kl = jax.random.split(jax.random.PRNGKey(0), 3)
    b = jax.random.randint(kb, (nb, stored, BLOCK), 0, bins, jnp.uint8)
    b = b.at[:, live:].set(0)
    g = jax.random.normal(kg, (nb * BLOCK,), jnp.float32)
    stats = pack_stats(g, jnp.abs(g) + 0.1,
                       jnp.ones(nb * BLOCK, jnp.float32), "hilo")
    leaf = jax.random.randint(kl, (nb, BLOCK), 0, 255, jnp.int32)
    return b, stats.reshape(stats.shape[0], nb, BLOCK), leaf


def time_call(ops, bins: int, live: int, slots: int, iters: int):
    fn = jax.jit(lambda b, s, l, k: build_histogram_batched_t(
        b, s, l, k, bins, "hilo", impl="pallas2", live_columns=live))
    args = ops + (jnp.arange(slots, dtype=jnp.int32),)
    t0 = time.perf_counter()
    jax.block_until_ready(fn(*args))
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e3, first_s


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--rows", type=int, default=26 << 20,
                    help="rows per call of the 32-column shapes (and at "
                         "most this many of the Criteo shard's), cut to "
                         "whole 8192-row blocks")
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--out", default="chiprun_out/hist_microbench.jsonl")
    a = ap.parse_args()
    dev = jax.devices()[0]
    print(f"platform={dev.platform} device_kind={dev.device_kind} "
          f"block={BLOCK} hilo u8", flush=True)
    # the MXU's time for the contraction as run (live columns, bins padded
    # to the sublane tile, one 128-lane tile of slots x planes), where the
    # device's peak is published; a CPU rehearsal has none
    peak = PEAKS.get(dev.device_kind, {}).get("bf16_flops")
    points, table = [], []
    for bins, stored, live, rows in SHAPES:
        rows = min(rows or a.rows, a.rows) // BLOCK * BLOCK
        bp = -(-bins // 8) * 8
        ops = operands(rows, bins, stored, live)
        mxu_ms = peak and 2.0 * rows * live * bp * 128 / peak * 1e3
        mxu_txt = f"{mxu_ms:.1f} ms" if peak else "n/a"
        ms = {}
        for slots in SLOTS:
            fblk, chunks = perfeature_chunks(stored, bins, slots, 5)
            per_dot = perfeature_columns_per_dot(bins, BLOCK, "hilo", fblk,
                                                 live)
            ms[slots], first_s = time_call(ops, bins, live, slots, a.iters)
            points.append({
                "platform": dev.platform, "device_kind": dev.device_kind,
                "rows": rows, "bins": bins, "stored_columns": stored,
                "live_columns": live, "slots": slots,
                "feature_chunks": chunks, "columns_per_dot": per_dot,
                "ms_per_call": ms[slots], "first_call_s": first_s,
                "mxu_tile_ms": mxu_ms})
            print(f"B={bins:3d} live={live:2d}/{stored} K={slots:2d} "
                  f"chunks={chunks} G={per_dot:2d}: {ms[slots]:8.2f} ms/call"
                  f"  (MXU tile {mxu_txt}, first call {first_s:5.1f} s)",
                  flush=True)
        table.append(f"| {bins} | {live} of {stored} | {rows} | {per_dot} | "
                     + " | ".join(f"{ms[k]:.1f}" for k in SLOTS)
                     + f" | {(ms[25] - ms[1]) / 24:.2f} |")
    os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
    with open(a.out, "a") as f:
        for p in points:
            f.write(json.dumps(p) + "\n")
    print("\n| B | live | rows | G | " + " | ".join(f"K={k}" for k in SLOTS)
          + " | ms per slot (25 vs 1) |")
    print("|---|---|---|---|" + "---|" * (len(SLOTS) + 1))
    print("\n".join(table))

if __name__ == "__main__":
    main()
