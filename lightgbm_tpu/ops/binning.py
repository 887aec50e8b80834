"""Device-parallel dataset binning: chunked jitted value->bin kernel.

The ingest analog of ops/predict.py: raw rows are quantized into bin ids
on the accelerator instead of column-by-column host numpy.  The kernel
is a batched searchsorted — for every (row, feature) it counts how many
of the feature's bin upper bounds are strictly below the value, which is
exactly `np.searchsorted(ub[:hi], v, side="left")`
(`BinMapper.values_to_bins`, the reference `BinMapper::ValueToBin`,
bin.h:472-508).

Bitwise parity on EVERY backend is non-negotiable (the training bins
feed split decisions), but accelerators run f32 while the host bounds
are f64.  The kernel therefore never compares floats: each f64 is mapped
on the host to its MONOTONE int64 key (sign-flipped IEEE bit pattern —
total order identical to the f64 order, with -0.0 == +0.0 keying to the
same value), shipped as two planes (hi int32, lo uint32), and compared
lexicographically on device.  Integer compares are exact everywhere, so
the device bins match `values_to_bins` bit-for-bit even in x32 mode.

NaN rides a reserved key (INT64_MAX, unreachable by finite/inf keys) and
is routed per the feature's MissingType: last bin when NaN-missing, the
0.0 bin (`default_bin`) otherwise.  Categorical features look up a
flattened per-feature category->bin table; negative / unseen / too-large
categories fall to the last bin like `value_to_bin`.

`DeviceBinner` streams `[chunk, F]` blocks: the host computes chunk
i+1's key planes (cheap vectorized bit twiddling) while the device bins
chunk i — transfer and compute overlap through jax's async dispatch —
and the full `[n, F]` matrix is assembled device-side, never
materialized on the host unless a host consumer asks (see
`TrainingData.bins`).  For a learner that shards rows over several chips
of one process the chunks go to the chips in consecutive row ranges and
stay there (`RowParts`): no chip ever holds the whole table.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from .. import obs
from ..io.bin_mapper import BinMapper, BinType, MissingType, sort_keys
from ..utils import membudget
from ..utils.compile_ledger import ledger_jit

_NAN_KEY = np.int64(np.iinfo(np.int64).max)
_NAN_KEY_HI = np.int32(_NAN_KEY >> 32)
_NAN_KEY_LO = np.uint32(_NAN_KEY & 0xFFFFFFFF)
_NAN_CAT = -2  # host-side category sentinel for NaN values
# host key prep: a chunk over glibc's largest mmap threshold (freed blocks
# above it go back to the kernel) is keyed in slabs of this many values
# (2 MB of f64); see DeviceBinner._prep_chunk
_PREP_WHOLE_BYTES = 32 << 20
_PREP_SLAB_VALUES = 1 << 18
# per-feature / total category-LUT capacity: features with larger raw
# category ids fall back to host binning (pandas codes and typical int
# categories sit far below this)
_CAT_LUT_MAX = 1 << 16
_CAT_LUT_TOTAL_MAX = 1 << 22


def split_keys(keys: np.ndarray):
    """int64 keys -> (hi int32, lo uint32) planes for x32-safe compare."""
    return ((keys >> 32).astype(np.int32),
            (keys & np.int64(0xFFFFFFFF)).astype(np.uint32))


@ledger_jit(site="binning.chunk",
            static_argnames=("has_cat", "out_dtype"))
def _bin_chunk_kernel(vhi, vlo, cv, t: Dict[str, jnp.ndarray],
                      has_cat: bool, out_dtype: str):
    """[chunk, F] key planes (+ category codes) -> [chunk, F] bin ids.

    t: bhi/blo [F, B] bound-key planes (padded with the NaN key so
    padding never counts), num_bin/default_bin/nan_is_last [F], and —
    when has_cat — is_cat/cat_offset/cat_width/nan_cat_bin [F] plus the
    flattened category LUT.
    """
    # lexicographic (hi, lo) compare == int64 key compare == f64 '<'
    lt = (t["bhi"][None, :, :] < vhi[:, :, None]) | (
        (t["bhi"][None, :, :] == vhi[:, :, None])
        & (t["blo"][None, :, :] < vlo[:, :, None]))
    num = jnp.sum(lt, axis=-1, dtype=jnp.int32)
    is_nan = (vhi == _NAN_KEY_HI) & (vlo == _NAN_KEY_LO)
    last = t["num_bin"][None, :] - 1
    nan_bin = jnp.where(t["nan_is_last"][None, :] > 0, last,
                        t["default_bin"][None, :])
    out = jnp.where(is_nan, nan_bin, num)
    if has_cat:
        width = t["cat_width"][None, :]
        idx = t["cat_offset"][None, :] + jnp.clip(cv, 0, width - 1)
        catbin = jnp.take(t["cat_lut"], idx, axis=0)
        unseen = (cv < 0) | (cv >= width)
        catbin = jnp.where(unseen, last, catbin)
        catbin = jnp.where(cv == _NAN_CAT, t["nan_cat_bin"][None, :], catbin)
        out = jnp.where(t["is_cat"][None, :] > 0, catbin, out)
    return out.astype(out_dtype)


class RowParts:
    """An `[n, F]` bin matrix kept as consecutive row ranges, each a device
    array on the chip that binned it.  What a row-sharded learner lays its
    shards out from (`TPUTreeLearner._layout_device_bins`), chip by chip;
    a plain device matrix is the one-part case (`RowParts.of`)."""

    def __init__(self, parts: Sequence[jnp.ndarray]):
        self.parts = list(parts)
        self.starts = np.concatenate(
            [[0], np.cumsum([p.shape[0] for p in self.parts])]).astype(int)
        self.shape = (int(self.starts[-1]), self.parts[0].shape[1])
        self.dtype = self.parts[0].dtype

    @classmethod
    def of(cls, matrix) -> "RowParts":
        return matrix if isinstance(matrix, cls) else cls([matrix])

    def __array__(self, dtype=None, copy=None):
        out = np.concatenate([np.asarray(p) for p in self.parts])
        return out if dtype is None else out.astype(dtype, copy=False)

    def rows(self, lo: int, hi: int, device,
             cols: slice = slice(None)) -> List[jnp.ndarray]:
        """Rows [lo, hi) of the columns `cols` as pieces in row order, each
        on `device`: a part that lies on it already is not copied, the
        others' rows are cut where they lie and go chip to chip."""
        whole = cols == slice(None) or cols.indices(self.shape[1]) == (
            0, self.shape[1], 1)
        out = []
        for p, s in zip(self.parts, self.starts):
            a, b = max(lo - s, 0), min(hi - s, p.shape[0])
            if a < b:
                out.append(jax.device_put(
                    p if whole and (a, b) == (0, p.shape[0])
                    else p[a:b, cols], device))
        return out

    def gathered(self) -> jnp.ndarray:
        """The whole matrix as one array, on the first part's device."""
        if len(self.parts) == 1:
            return self.parts[0]
        device = next(iter(self.parts[0].devices()))
        return jnp.concatenate(self.rows(0, self.shape[0], device), axis=0)

    def column_counts(self, hit) -> np.ndarray:
        """Per column, the rows for which `hit(part)` holds: each part is
        reduced where it lies and only the [F] counts leave the device."""
        return sum(np.asarray(jnp.sum(hit(p), axis=0, dtype=jnp.int32))
                   .astype(np.int64) for p in self.parts)

    def take(self, idx: np.ndarray) -> np.ndarray:
        """Host copy of the rows at the ascending indices `idx`."""
        idx = np.asarray(idx)
        cut = np.searchsorted(idx, self.starts)
        return np.concatenate([
            np.asarray(p[idx[a:b] - s]) for p, s, a, b in
            zip(self.parts, self.starts, cut[:-1], cut[1:]) if a < b]
            or [np.zeros((0, self.shape[1]), self.dtype)])


class DeviceBinner:
    """Streams raw row chunks through the device bin kernel.

    Build once per mapper set (`DeviceBinner.build` returns None when a
    categorical feature's ids exceed the LUT capacity — callers fall
    back to host binning), then `bin_matrix(X)` yields the device
    `[n, F]` binned matrix in the dataset's storage dtype.
    """

    def __init__(self, tables: Dict[str, np.ndarray], used_cols: List[int],
                 has_cat: bool, out_dtype: np.dtype, chunk_rows: int):
        self.used_cols = used_cols
        self.has_cat = has_cat
        self.out_dtype = np.dtype(out_dtype)
        self.chunk_rows = max(int(chunk_rows), 256)
        self._cat_widths = tables["cat_width"].copy() if has_cat else None
        self._is_cat = tables["is_cat"].copy() if has_cat else None
        self._tables = tables
        self._dev_tables = {}  # device (None: the default one) -> tables
        self._launches = 0  # kernel launches so far: the spans' chunk tag

    # ------------------------------------------------------------------
    @classmethod
    def build(cls, mappers: Sequence[BinMapper], used_cols: Sequence[int],
              out_dtype, chunk_rows: int) -> Optional["DeviceBinner"]:
        used = [int(c) for c in used_cols]
        F = len(used)
        if F == 0:
            return None
        ms = [mappers[c] for c in used]
        # numerical bound tables: ub[:hi] keys, NaN-key padded
        his = [(m.num_bin - 1 - (1 if m.missing_type == MissingType.NAN
                                 else 0))
               if m.bin_type == BinType.NUMERICAL else 0 for m in ms]
        B = max(max(his), 1)
        bkeys = np.full((F, B), _NAN_KEY, np.int64)
        for j, (m, hi) in enumerate(zip(ms, his)):
            if hi > 0:
                bkeys[j, :hi] = sort_keys(m.bin_upper_bound[:hi])
        bhi, blo = split_keys(bkeys)
        num_bin = np.array([m.num_bin for m in ms], np.int32)
        default_bin = np.array([m.default_bin for m in ms], np.int32)
        nan_is_last = np.array(
            [int(m.missing_type == MissingType.NAN) for m in ms], np.int32)
        tables = {"bhi": bhi, "blo": blo, "num_bin": num_bin,
                  "default_bin": default_bin, "nan_is_last": nan_is_last}

        has_cat = any(m.bin_type == BinType.CATEGORICAL for m in ms)
        if has_cat:
            widths = np.zeros(F, np.int64)
            for j, m in enumerate(ms):
                if m.bin_type != BinType.CATEGORICAL:
                    continue
                real = [c for c in m.categorical_2_bin if c >= 0]
                w = (max(real) + 1) if real else 1
                if w > _CAT_LUT_MAX:
                    return None  # ids too large for a dense LUT
                widths[j] = w
            if widths.sum() > _CAT_LUT_TOTAL_MAX:
                return None
            offsets = np.concatenate([[0], np.cumsum(widths)[:-1]])
            lut = np.zeros(max(int(widths.sum()), 1), np.int32)
            nan_cat_bin = np.zeros(F, np.int32)
            for j, m in enumerate(ms):
                if m.bin_type != BinType.CATEGORICAL:
                    continue
                lo, w = int(offsets[j]), int(widths[j])
                lut[lo:lo + w] = m.num_bin - 1  # unmapped -> last bin
                for c, b in m.categorical_2_bin.items():
                    if 0 <= c < w:
                        lut[lo + c] = b
                # NaN: dedicated last bin when NaN-missing, else the
                # category-0 route (values_to_bins nan_cat semantics)
                nan_cat_bin[j] = (m.num_bin - 1
                                  if m.missing_type == MissingType.NAN
                                  else int(lut[lo]) if w > 0
                                  else m.num_bin - 1)
            tables.update({
                "is_cat": np.array(
                    [int(m.bin_type == BinType.CATEGORICAL) for m in ms],
                    np.int32),
                "cat_offset": offsets.astype(np.int32),
                "cat_width": widths.astype(np.int32),
                "cat_lut": lut,
                "nan_cat_bin": nan_cat_bin})
        return cls(tables, used, has_cat, out_dtype, chunk_rows)

    # ------------------------------------------------------------------
    def _prep_chunk(self, block: np.ndarray):
        """Raw f64 [rows, F] -> host key planes (+ category codes)."""
        vals = np.ascontiguousarray(block, dtype=np.float64)
        if vals.nbytes <= _PREP_WHOLE_BYTES:
            vhi, vlo = split_keys(sort_keys(vals))
        else:
            # `sort_keys` / `split_keys` make half a dozen int64
            # temporaries the size of their input.  Past 32 MiB (65536 rows
            # x 67 columns are 35 MB) glibc no longer reuses a freed block
            # and maps fresh pages for every one, and how long those page
            # faults take is the host's mood: 9 s or 31 s of staging for one
            # 13M-row table, run by run (PR 27, on the chip's host).  Keyed
            # in slabs the temporaries stay small and the time steady (15 s);
            # the planes are the same bit for bit.  Smaller chunks are not
            # slabbed, on one measurement that is not understood: alone the
            # slabs key a 28-column chunk three times as fast, but inside
            # `bin_stream` they cost a 27M-row table +6 s in 3 runs of 4
            # (PERF.md section 7)
            vhi = np.empty(vals.shape, np.int32)
            vlo = np.empty(vals.shape, np.uint32)
            step = max(1, _PREP_SLAB_VALUES // vals.shape[1])
            for lo in range(0, vals.shape[0], step):
                vhi[lo:lo + step], vlo[lo:lo + step] = split_keys(
                    sort_keys(vals[lo:lo + step]))
        cv = None
        if self.has_cat:
            # int(v) truncation toward zero; NaN -> sentinel; clip keeps
            # the int32 cast defined for huge/inf values (they are
            # unseen either way)
            isnan = np.isnan(vals)
            t = np.clip(np.trunc(np.where(isnan, -1.0, vals)), -1.0,
                        float(_CAT_LUT_MAX)).astype(np.int32)
            cv = np.where(isnan, np.int32(_NAN_CAT), t)
        return vhi, vlo, cv

    def bin_chunk(self, block: np.ndarray, device=None) -> jnp.ndarray:
        """Bin one [rows, F] raw block (guarded ingest-upload site) on
        `device`, the default one where None.

        A classified device OOM halves `chunk_rows` and re-bins the
        block in smaller launches — bins are bit-identical at ANY chunk
        size (the PR-3 chunk-boundary contract), so the recovery is
        invisible to training; at the kernel's floor the structured
        DeviceOutOfMemory propagates."""
        rows = block.shape[0]
        if rows == 0:
            return jnp.zeros((0, block.shape[1]), self.out_dtype)
        parts = []
        lo = 0
        while lo < rows:
            sub = block[lo:lo + self.chunk_rows]
            try:
                with membudget.oom_guard("ingest_chunk",
                                         rows=int(sub.shape[0])):
                    parts.append(self._bin_chunk_once(sub, device))
                lo += sub.shape[0]
            except membudget.DeviceOutOfMemory:
                if not self._shrink_chunk():
                    raise
        if len(parts) == 1:
            return parts[0]
        # the reassembled full block is the single largest allocation
        # here, and a multi-part reassembly only happens right after a
        # shrink — i.e. on a nearly-full device.  Shrinking further
        # cannot help (the output is full-block regardless), so a
        # failure classifies and propagates structured for the
        # mid-train ladder above instead of escaping raw
        with membudget.oom_guard("ingest_chunk", rows=int(rows),
                                 stage="reassemble"):
            return jnp.concatenate(parts, axis=0)

    def _shrink_chunk(self) -> bool:
        """Halve this binner's LOCAL chunk after a classified OOM
        (floor 256, the kernel minimum — below the ladder's 4096 param
        floor because the in-flight stream must finish even on a very
        tight device); logged + counted like every ladder step.  The
        recorded field names the binner-local width, NOT the
        tpu_ingest_chunk_rows param — the config is untouched here
        (the mid-train ladder owns param changes)."""
        from ..utils.log import Log

        if self.chunk_rows <= 256:
            return False
        new = max(self.chunk_rows // 2, 256)
        membudget.note_ladder_step("ingest_chunk", "shrink_chunk_rows",
                                   {"binner_chunk_rows": new})
        Log.warning(f"device OOM in chunked ingest: shrinking the "
                    f"binning chunk {self.chunk_rows} -> {new} and "
                    "re-binning (bins are chunk-invariant)")
        self.chunk_rows = new
        return True

    def _bin_chunk_once(self, block: np.ndarray, device=None) -> jnp.ndarray:
        """One [rows, F] kernel launch, padded to the chunk shape so
        every launch reuses ONE compiled program, slicing the pad off
        on device."""
        rows = block.shape[0]
        pad = self.chunk_rows - rows if rows < self.chunk_rows else 0
        if pad:
            block = np.concatenate(
                [block, np.zeros((pad, block.shape[1]), block.dtype)])
        chunk, self._launches = self._launches, self._launches + 1
        with obs.span("ingest/stage", chunk=chunk, rows=rows):
            vhi, vlo, cv = self._prep_chunk(block)
        if cv is None:
            cv = np.zeros((0,), np.int32)
        with obs.span("ingest/dispatch", chunk=chunk):
            if device not in self._dev_tables:
                self._dev_tables[device] = jax.device_put(self._tables, device)
            out = _bin_chunk_kernel(
                *jax.device_put((vhi, vlo, cv), device),
                self._dev_tables[device], self.has_cat, str(self.out_dtype))
            return out[:rows] if pad else out

    def bin_matrix(self, X: np.ndarray, devices=None):
        """Stream X's used columns through the kernel chunk by chunk.

        Dispatch is async: while the device bins chunk i, the host is
        already building chunk i+1's key planes, overlapping transfer
        with compute (the "Out-of-Core GPU Gradient Boosting" chunked
        ingest pattern).
        """
        return self.bin_stream([X], devices, X.shape[0])

    def bin_stream(self, blocks, devices=None, total_rows: int = 0):
        """Bin an iterable of raw row blocks, re-chunking across block
        boundaries so only the FINAL kernel launch pads — a file
        reader's chunk size rarely aligns with `chunk_rows`, and padding
        every reader chunk's tail would waste a steady fraction of the
        kernel work on long streams.

        With `devices` the `total_rows` rows are dealt to them in
        consecutive ranges of whole chunks, each chunk binned on the
        device that keeps it, and the result is a `RowParts`."""
        per = self.chunk_rows * max(
            1, -(-total_rows // (len(devices or [0]) * self.chunk_rows)))
        parts: list = [[] for _ in devices or [0]]
        done = 0  # rows handed to a device so far

        def emit(rows_block):
            nonlocal done
            k = min(done // per, len(parts) - 1)
            parts[k].append(self.bin_chunk(
                rows_block, devices[k] if devices else None))
            done += rows_block.shape[0]

        pend: list = []
        pend_rows = 0
        for block in blocks:
            b = np.asarray(block, dtype=np.float64)
            if self.used_cols != list(range(b.shape[1])):
                b = b[:, self.used_cols]  # a copy: only where columns drop
            pend.append(b)
            pend_rows += b.shape[0]
            while pend_rows >= self.chunk_rows:
                buf = pend[0] if len(pend) == 1 else np.concatenate(pend)
                # snapshot the slice width BEFORE the call: an OOM
                # recovery inside bin_chunk SHRINKS self.chunk_rows,
                # and re-reading it for the remainder slice would keep
                # rows the call already binned (silent duplication)
                c = self.chunk_rows
                emit(buf[:c])
                pend = [buf[c:]]
                pend_rows = pend[0].shape[0]
        if pend_rows > 0 or not done:
            if not pend:
                return jnp.zeros((0, len(self.used_cols)), self.out_dtype)
            emit(pend[0] if len(pend) == 1 else np.concatenate(pend))
        whole = [p[0] if len(p) == 1 else jnp.concatenate(p, axis=0)
                 for p in parts if p]
        return RowParts(whole) if devices else whole[0]
