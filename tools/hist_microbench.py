"""Time the perfeature histogram kernel alone, at the benchmark cells' shape.

One `pallas2` call per (bins, columns, slots) point over `--rows` rows of
u8 bins in 8192-row blocks, hilo statistics: what `%hist_build*` costs per
call inside the grow program, without the program around it.  The sweep is
the cells' own axes: B in {63, 255}; 32 stored columns of which 28 or all 32
are live (`live_columns`, ops/histogram.py); K in {1, 4, 16, 25} slots, the
ramp's widths and the loop's.  PERF.md §5 keeps the last table: the per-slot
term it shows is what the next kernel change starts from.

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
from lightgbm_tpu.ops.histogram import build_histogram_batched_t, pack_stats

BLOCK = 8192
STORED = 32


def operands(rows: int, bins: int, live: int):
    """Device-made operands of one call: [nb, 32, 8192] u8 bins whose
    columns past `live` are the learner's padding (constant bin 0)."""
    nb = rows // BLOCK
    kb, kg, kl = jax.random.split(jax.random.PRNGKey(0), 3)
    b = jax.random.randint(kb, (nb, STORED, BLOCK), 0, bins, jnp.uint8)
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
                    help="rows per call, cut to whole 8192-row blocks")
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--out", default="chiprun_out/hist_microbench.jsonl")
    a = ap.parse_args()
    dev = jax.devices()[0]
    rows = a.rows // BLOCK * BLOCK
    print(f"platform={dev.platform} device_kind={dev.device_kind} "
          f"rows={rows} block={BLOCK} stored_columns={STORED} hilo u8",
          flush=True)
    # the MXU's time for the contraction as run (live columns, bins padded
    # to the sublane tile, one 128-lane tile of slots x planes), where the
    # device's peak is published; a CPU rehearsal has none
    peak = PEAKS.get(dev.device_kind, {}).get("bf16_flops")
    points = []
    for bins in (63, 255):
        bp = -(-bins // 8) * 8
        for live in (28, STORED):
            ops = operands(rows, bins, live)
            mxu_ms = peak and 2.0 * rows * live * bp * 128 / peak * 1e3
            for slots in (1, 4, 16, 25):
                ms, first_s = time_call(ops, bins, live, slots, a.iters)
                points.append({
                    "platform": dev.platform, "device_kind": dev.device_kind,
                    "rows": rows, "bins": bins, "live_columns": live,
                    "slots": slots, "ms_per_call": ms,
                    "first_call_s": first_s, "mxu_tile_ms": mxu_ms})
                mxu_txt = f"{mxu_ms:.1f} ms" if peak else "n/a"
                print(f"B={bins:3d} live={live:2d}/{STORED} K={slots:2d}: "
                      f"{ms:8.2f} ms/call  (MXU tile {mxu_txt}, "
                      f"first call {first_s:5.1f} s)", flush=True)
    os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
    with open(a.out, "a") as f:
        for p in points:
            f.write(json.dumps(p) + "\n")
    print("\n| B | live | K=1 | K=4 | K=16 | K=25 | ms per slot (25 vs 1) |")
    print("|---|---|---|---|---|---|---|")
    for bins in (63, 255):
        for live in (28, STORED):
            ms = {p["slots"]: p["ms_per_call"] for p in points
                  if (p["bins"], p["live_columns"]) == (bins, live)}
            print(f"| {bins} | {live} of {STORED} | "
                  + " | ".join(f"{ms[k]:.1f}" for k in (1, 4, 16, 25))
                  + f" | {(ms[25] - ms[1]) / 24:.2f} |")


if __name__ == "__main__":
    main()
