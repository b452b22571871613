"""Operations and bytes the FM ALGORITHM needs, from shapes and the
batch's own unique-id count -- never from how a step is implemented.

The same counts hold whether an XLA scatter, the tile kernels or a future
table layout runs the step: a row is ``4 * (1 + k)`` logical bytes (36 at
k=8), never the padded 64 B at rest or 512 B a tiled copy takes.
"""

F32 = 4


def row_bytes(k: int) -> int:
    return F32 * (1 + k)


def fm_forward_flops(n: int, f: int, k: int) -> int:
    """score = w0 + sum_j w_j x_j + 0.5 sum_c [(sum_j v_jc x_j)^2 -
    sum_j (v_jc x_j)^2], per example: linear 2F; xv F*k; its two sums
    F*k adds + F*k (mul + add); closing 3k + 2."""
    return n * (f * (2 + 4 * k) + 3 * k + 2)


def fm_backward_flops(n: int, f: int, k: int) -> int:
    """Per occurrence: d/dw = g x (1 mul), d/dv_c = g x (s1_c - v_c x)
    (sub, 2 mul, reusing v x: 4); plus the logistic residual per
    example."""
    return n * (f * (1 + 4 * k) + 4)


def train_step_needed(n: int, f: int, k: int, n_unique: int) -> dict:
    """One sparse Adagrad FM step on a batch of ``n`` examples touching
    ``n_unique`` distinct rows: read each unique row once for the gather;
    read and write its table row and its accumulator row once for the
    apply; read the batch's ids, values, labels and weights."""
    rb = row_bytes(k)
    gather = n_unique * rb
    apply_ = n_unique * rb * 4  # table r+w, accumulator r+w
    batch = n * f * (4 + F32) + n * 2 * F32
    # apply arithmetic: g^2, acc add, rsqrt, mul, mul, sub per element
    flops = (fm_forward_flops(n, f, k) + fm_backward_flops(n, f, k)
             + n_unique * (1 + k) * 6)
    return {"bytes": gather + apply_ + batch, "flops": flops}


def serve_needed(n: int, f: int, k: int, n_unique: int) -> dict:
    """Scoring ``n`` examples touching ``n_unique`` distinct rows: read
    each once, read ids and values, write one score per example."""
    return {
        "bytes": n_unique * row_bytes(k) + n * f * (4 + F32) + n * F32,
        "flops": fm_forward_flops(n, f, k) + 4 * n,  # + sigmoid
    }
