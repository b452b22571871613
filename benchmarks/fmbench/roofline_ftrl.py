"""Operations and bytes the FM's FTRL-Proximal step needs (McMahan et al.
2013, Algorithm 1, per coordinate), from shapes and the batch's own
unique-id count -- never from how a step is implemented.

The forward and backward of the FM, the gather and the batch are
``roofline.py``'s.  What FTRL changes is the apply: each unique row's
weights ``w`` and both accumulators ``z`` and ``n`` are read once and
written once -- 6 row-bytes a unique row, where Adagrad's table and
accumulator are 4 -- and its arithmetic a coordinate, counted from the
formula as written:

    n' = n + g2                          1  (g2, the occurrences' sum of
                                             g^2, is counted per
                                             occurrence: 3 an element with
                                             g^2 and the two sums' adds)
    sigma = (sqrt(n') - sqrt(n)) / alpha 4  (two square roots, subtract,
                                             divide)
    z' = z + g - sigma * w               3
    denom = (beta + sqrt(n')) / alpha    3  (sqrt(n') is the one above:
            + lambda2                        add, divide, add)
    w' = 0 if |z'| <= lambda1 else       7  (abs, compare, sign, times
         -(z' - sgn(z') lambda1) / denom     lambda1, subtract, divide,
                                             select)

= 18 operations a coordinate of a unique row, where Adagrad's update is
counted at 6 with its g^2 (``roofline.train_step_needed``).  A row is
``4 * (1 + k)`` logical bytes (36 at k = 8).  What a writer pays to
move a row as it rests (the stream writer reads and writes every row of
every table) is the layout's cost, not the algorithm's: not counted.
"""

from fmbench import roofline

F32 = roofline.F32
FTRL_FLOPS = 18  # a coordinate of a unique row, derived above
TABLES = 3  # w, z, n


def apply_needed(k: int, n_unique: int) -> dict:
    """The apply's own work a step: every unique row of the three tables
    read and written once, FTRL's update on each of its coordinates."""
    return {"bytes": n_unique * roofline.row_bytes(k) * 2 * TABLES,
            "flops": n_unique * (1 + k) * FTRL_FLOPS}


def train_step_needed(n: int, f: int, k: int, n_unique: int) -> dict:
    """One sparse FTRL FM step on a batch of ``n`` examples touching
    ``n_unique`` distinct rows: read each unique row once for the gather;
    the apply as ``apply_needed``; read the batch's ids, values, labels
    and weights; the FM's forward and backward, and per occurrence
    element g^2 and the adds into the row's two sums (3)."""
    rb = roofline.row_bytes(k)
    apply_ = apply_needed(k, n_unique)
    batch = n * f * (4 + F32) + n * 2 * F32
    flops = (roofline.fm_forward_flops(n, f, k)
             + roofline.fm_backward_flops(n, f, k)
             + n * f * (1 + k) * 3 + apply_["flops"])
    return {"bytes": n_unique * rb + apply_["bytes"] + batch,
            "flops": flops}
