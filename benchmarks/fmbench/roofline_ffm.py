"""Operations and bytes the field-aware FM ALGORITHM needs (Juan et al.
2016, as LIBFFM computes it), from shapes and the batch's own unique-id
count -- never from how a step is implemented.

A row is ``4 * (1 + fields * k)`` logical bytes (628 at 39 fields,
k = 4), never what a layout pads it to.  The interaction is the
``F (F - 1) / 2`` pairs of an example's features, each a dot of two
``k``-vectors, forward and backward: the program's field-grouped one-hot
matmuls do ~39 times more multiplications, and those are not counted.
"""

F32 = 4


def row_bytes(fields: int, k: int) -> int:
    return F32 * (1 + fields * k)


def pairs(f: int) -> int:
    return f * (f - 1) // 2


def ffm_forward_flops(n: int, f: int, k: int) -> int:
    """Per example: linear 2F; per pair the dot (k mul, k - 1 add), the
    two values (2 mul) and the add into the score: 2k + 2."""
    return n * (2 * f + pairs(f) * (2 * k + 2))


def ffm_backward_flops(n: int, f: int, k: int) -> int:
    """Per pair: kappa = g x_i x_j (2 mul), then k mul into each of the
    two factor vectors' gradients: 2k + 2; per occurrence d/dw = g x;
    the logistic residual per example (4)."""
    return n * (pairs(f) * (2 * k + 2) + f + 4)


def train_step_needed(n: int, f: int, fields: int, k: int,
                      n_unique: int) -> dict:
    """One sparse Adagrad FFM step on a batch of ``n`` examples touching
    ``n_unique`` distinct rows: read each unique row once for the gather;
    read and write its table row and its accumulator row once for the
    apply; read the batch's ids, values, fields, labels and weights:
    what must cross HBM if nothing were materialised between the step's
    parts.  Arithmetic beside the interaction: g^2 and the add into the
    row's sums per occurrence element (3); acc add, rsqrt, mul, mul, sub
    per unique element (5)."""
    rb = row_bytes(fields, k)
    gather = n_unique * rb
    apply_ = n_unique * rb * 4  # table r+w, accumulator r+w
    batch = n * f * (4 + F32 + 4) + n * 2 * F32
    d = 1 + fields * k
    flops = (ffm_forward_flops(n, f, k) + ffm_backward_flops(n, f, k)
             + n * f * d * 3 + n_unique * d * 5)
    return {"bytes": gather + apply_ + batch, "flops": flops}
