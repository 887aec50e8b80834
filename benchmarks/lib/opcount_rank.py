"""Operations and bytes of LambdaRank's gradient pass, from the data set's
shape: the yardstick of `lambda_grad_roofline`.  As `lib/opcount.py`, this
counts what the equations need, whatever implements them (padding slots, a
rank pass in place of a sort, the walk between rows and queries are the
implementation's and are not counted).

    bytes      = rows * (4 + 4 + 4 + 4 + 4)
                 the score, the label and the query of every row read once;
                 lambda and hessian written once, float32 and int32
    operations = ordered_pairs / 2 * PAIR_OPS + rows * ROW_OPS

`ordered_pairs` is what the program's gauge `lgbm_rank_pairs{kind="valid"}`
counts: pairs of rows of one query with different labels, from both rows.
Per unordered pair the equations take PAIR_OPS = 24 floating-point
operations: the score difference (1), the gains' (1), the discounts' and
its magnitude (2), their product with 1/maxDCG (2), the norm's |D|, sum and
division (3), sigma * D and its clip (3), exp, 1 + exp and the reciprocal
(3), sigma * delta * p (2), two lambda updates (2), (1 - p), its product
and two hessian updates (4), the query's sum (1).  Per row ROW_OPS = 8: its
rank's discount (log2, sum, reciprocal) and the query's factor and the two
products, amortised.  A comparison sort's n log n is left out: it is small
beside the pairs and a rank needs no sort.
"""

PAIR_OPS = 24
ROW_OPS = 8
ROW_BYTES = 20


def lambda_grad(rows: int, ordered_pairs: float):
    """(operations, bytes) of one gradient pass."""
    return ordered_pairs / 2 * PAIR_OPS + rows * ROW_OPS, rows * ROW_BYTES
