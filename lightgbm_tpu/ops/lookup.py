"""`table[ids]` for a small f32 table and many ids: the score update's lookup
of a row's leaf value (`models/learner.py` `_post`, `models/gbdt.py`
`_renew_and_update`).

XLA's gather on a TPU costs 8-10 ns a row (265 ms an iteration for 27.3M
rows: PERF.md §5), so there the lookup is a contraction of each row's one-hot
over the table instead, which the compiler fuses into the MXU operand without
writing the one-hot out.  The table goes through as the four bytes of each
entry's bit pattern, so the result is the entry to the bit (-0.0, inf and NaN
included) and a non-finite entry touches only its own rows.
"""

import jax
import jax.numpy as jnp
from jax import lax

from ..utils.compile_ledger import ledger_jit

# On a v5e at 27,262,976 rows XLA's gather takes 147-225 ms from 65 entries on,
# whatever the table's size; the contraction's time grows with the table, 4.3 ms
# at 31 entries, 8.8 at 255, 84 at 4,095, 167 at 8,191, 330 at 16,383, and meets
# the gather's near 9,600.  The constant stands where the contraction still takes
# under half the gather's time (PERF.md §5, PR 31).
ONEHOT_MAX_ENTRIES = 4096


def lookup_form(platform: str, entries: int) -> str:
    """The rule: "onehot" on a TPU up to well under the measured crossover,
    the gather everywhere else (on CPU it is the fast form, and the tests'
    reference)."""
    if platform == "tpu" and entries <= ONEHOT_MAX_ENTRIES:
        return "onehot"
    return "gather"


def _onehot_lookup(table, ids):
    entries = table.shape[0]
    bits = lax.bitcast_convert_type(table, jnp.int32)
    # a byte is exact in bf16, and each row's sum has one non-zero term
    planes = jnp.stack([(bits >> s) & 255 for s in (0, 8, 16, 24)]
                       ).astype(jnp.bfloat16)
    onehot = (ids[None, :] == lax.broadcasted_iota(jnp.int32, (entries, 1), 0)
              ).astype(jnp.bfloat16)
    b = jnp.dot(planes, onehot, preferred_element_type=jnp.float32
                ).astype(jnp.int32)
    word = b[0] | (b[1] << 8) | (b[2] << 16) | (b[3] << 24)
    return lax.bitcast_convert_type(word, jnp.float32)


@ledger_jit(site="lookup.lookup", static_argnames=("form",))
def _lookup(table, ids, form):
    return _onehot_lookup(table, ids) if form == "onehot" else table[ids]


def lookup(table, ids):
    """table[ids]: `table` f32 [L], `ids` int32 [n] in [0, L) -> f32 [n].
    One program when called eagerly; inlined when called under a trace."""
    return _lookup(table, ids, form=lookup_form(
        jax.devices()[0].platform, table.shape[0]))
