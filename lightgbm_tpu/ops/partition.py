"""The row partition of one grower round as ONE pass over the leaf ids.

`exec_round`'s "select" lowering sweeps the `[n]` leaf ids once per split
slot (Kr unrolled XLA fusions a round, 296 sweeps a 255-leaf tree); here a
Pallas kernel reads a block of ids once, applies all Kr splits to it in
registers and writes it back in place: 15 sweeps a tree.

Layout.  Nothing is copied on the way in: both operands are views XLA takes
as bitcasts of what the grower already holds.

* leaf ids `[n]` int32 -> `[n/128, 128]`: a block of R sublane-rows fills
  whole vector registers (a `[1, block]` row would fill an eighth of each).
* bins `[F, n]` -> `[F/8, n/128, 8, 128]`: the matrix is stored in tiles of
  8 columns x 128 rows (narrow dtypes pack `4 / itemsize` adjacent columns
  into each 32-bit word), tile rows outermost, so this view is the same
  bytes.  In VMEM a block is `[F/8, R * W, 128]` words (W = 8 / pack word
  rows a tile) and column f's values for sublane-rows r0.. are the words
  `[f // 8, r0 * W + (f % 8) // pack :: W]`, one sublane-strided load per
  register, shifted and masked to the column's byte at 32 bits (the VPU
  compares no narrower integer).

The decision is `split.numeric_go_left`'s, folded to two compares by
`split.go_right_scalars`.  A row sits in at most one frontier leaf and the
new ids are fresh, so applying the slots one after the other to the running
ids equals `select`'s update from the round's old ids.
"""

import jax
import jax.numpy as jnp

from . import histogram

# sublane-rows (128 table rows each) per grid step at most, per register
# chunk, and the bytes of the bins block a step may hold in VMEM (twice, the
# pipeline's two buffers)
_STEP_ROWS = 256
_CHUNK_ROWS = 64
_BINS_BLOCK_BYTES = 4 << 20
# the widest table row, in bytes of bins, the rule hands to the kernel: it
# reads the whole matrix every round where `select` reads Kr columns and
# the ids Kr times, which is the cheaper from a few hundred bytes a row on
PARTITION_KERNEL_ROW_BYTES = 256
# scalar fields of a slot, the rows of the SMEM operand: where its column
# lies in a block of words (tile row, word row of the tile, bit shift in the
# word), then what the rows' bins and ids are compared with
_SLOT_FIELDS = 7


def partition_steps(n_rows: int, columns: int, itemsize: int):
    """(R, chunk) sublane-rows per grid step and per register chunk for a
    `[columns, n_rows]` bin matrix, or None where the kernel's views do not
    exist: rows are taken 128 to a sublane-row and columns 8 to a tile, of
    uint8 or int32 bins.  A row count that is no multiple of 1024 runs as
    one whole block, which interpret mode takes at any size and Mosaic only
    where it is small."""
    if (n_rows <= 0 or n_rows % 128 or columns % 8
            or itemsize not in (1, 4)):
        return None
    C = n_rows // 128
    fits = _BINS_BLOCK_BYTES // (columns * itemsize * 128)
    if C % 8:
        return (C, C) if C <= fits else None
    top = min(C, _STEP_ROWS, max(fits, 8))
    R = max(d for d in range(8, top + 1, 8) if C % d == 0)
    chunk = max(c for c in (8, 16, 32, _CHUNK_ROWS) if R % c == 0)
    return R, chunk


def partition_kernel_fits(n_rows: int, columns: int, itemsize: int) -> bool:
    """Whether the auto rule takes the kernel for this matrix on a TPU:
    whole registers (rows by 1024) and a table row narrow enough that one
    read of the matrix beats Kr sweeps of the ids."""
    return (n_rows % 1024 == 0
            and columns * itemsize <= PARTITION_KERNEL_ROW_BYTES
            and partition_steps(n_rows, columns, itemsize) is not None)


def _kernel(T: int, R: int, chunk: int, Kr: int, itemsize: int):
    """The kernel over one block: R sublane-rows of ids and of T tile rows
    of bins, swept in register chunks, Kr slots applied to each."""
    from jax.experimental import pallas as pl

    W = 2 * itemsize              # word rows of a tile of 8 columns

    def kernel(sc_ref, leaf_ref, bins_ref, out_ref):
        words = bins_ref
        if itemsize < 4:
            words = words.bitcast(jnp.int32)             # [T, R, W, 128]
        words = words.reshape(T, R * W, 128)

        # a slot's scalars, read once a block, not once a chunk
        slots = [[sc_ref[j, k] for j in range(_SLOT_FIELDS)]
                 for k in range(Kr)]

        def sweep(c, carry):
            r0 = pl.multiple_of(c * chunk, chunk)
            ids = leaf_ref[pl.ds(r0, chunk), :]
            for tile, word_row, shift, thr, flip, sel, new in slots:
                col = words[tile, pl.ds(r0 * W + word_row, chunk,
                                        stride=W), :]
                if itemsize < 4:
                    col = (col >> shift) & ((1 << 8 * itemsize) - 1)
                ids = jnp.where((ids == sel) & ((col > thr) ^ (col == flip)),
                                new, ids)
            out_ref[pl.ds(r0, chunk), :] = ids
            return carry

        jax.lax.fori_loop(0, R // chunk, sweep, 0)

    return kernel


def partition_rows(bins_t, leaf_ids, sel, new_ids, feat, thr, flip_bin):
    """The round's new leaf ids `[n]` int32.

    bins_t `[F, n]` uint8 or int32, leaf_ids `[n]` int32; per slot k (all
    `[Kr]` int32): rows of leaf `sel[k]` (-1: the slot splits nothing) whose
    bin in column `feat[k]` is `> thr[k]`, the answer inverted at bin
    `flip_bin[k]` (`split.go_right_scalars`), move to leaf `new_ids[k]`."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    F, n = bins_t.shape
    itemsize = bins_t.dtype.itemsize
    steps = partition_steps(n, F, itemsize)
    if steps is None:
        raise ValueError(
            f"the partition kernel takes uint8 or int32 bins, rows by 128 "
            f"(by 1024 unless the matrix is one small block) and columns "
            f"by 8; got {bins_t.dtype}[{F}, {n}]: use "
            f"tpu_partition_impl=select")
    R, chunk = steps
    C, T = n // 128, F // 8
    pack = 4 // itemsize          # columns a 32-bit word packs
    scalars = jnp.stack([feat // 8, feat % 8 // pack,
                         feat % pack * (8 * itemsize),
                         thr, flip_bin, sel, new_ids]).astype(jnp.int32)
    with jax.named_scope("partition"):
        out = pl.pallas_call(
            _kernel(T, R, chunk, sel.shape[0], itemsize),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(C // R,),
                in_specs=[pl.BlockSpec((R, 128), lambda i, sc: (i, 0)),
                          pl.BlockSpec((T, R, 8, 128),
                                       lambda i, sc: (0, i, 0, 0))],
                out_specs=pl.BlockSpec((R, 128), lambda i, sc: (i, 0))),
            out_shape=jax.ShapeDtypeStruct((C, 128), jnp.int32),
            # operand 1 (after the scalars) is the ids: rewritten in place,
            # the pass adds no [n] buffer to the program's peak
            input_output_aliases={1: 0},
            interpret=histogram.pallas_interpret(),
            name="partition_rows",
        )(scalars, leaf_ids.reshape(C, 128),
          bins_t.reshape(T, 8, C, 128).transpose(0, 2, 1, 3))
    return out.reshape(n)
