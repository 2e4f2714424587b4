"""Operations and bytes that a unit of work requires, from its shapes.

Every count is of the *required* work on the unpadded logical shapes,
whatever implements it: padding, recomputation and extra passes an
implementation makes are not counted, so a roofline share reads how close
the implementation comes to the least the algorithm needs.

All counts assume float32 storage of data, state and factors (4 bytes an
element), as the configurations state. A change of storage type changes
what is required, and needs a benchmark change to recount.

Each function returns ``(flops, bytes)``; a multiply-add counts 2 flops.
"""
from __future__ import annotations

F32 = 4


def power_matvec_call(n: int, m: int) -> tuple:
    """One streaming ``A @ v`` (or ``A.T @ u``) over an (n, m) matrix:
    A read once, the vector read and the result written."""
    return 2.0 * n * m, F32 * (n * m + n + m)


def factor_matvec_batch(b: int, d: int, m: int, rank: int) -> tuple:
    """Scoring b request rows against live-rank factors:
    ``((x @ U.T) * s) @ V`` with x (b, d), U (rank, d), s (rank,), V (rank, m).
    Reads x, U, s, V once and writes the (b, m) scores."""
    flops = 2.0 * b * rank * (d + m) + b * rank
    nbytes = F32 * (b * d + rank * d + rank + rank * m + b * m)
    return flops, nbytes


def logistic_power(n: int, d: int, m: int, k_iters: int) -> tuple:
    """The kernel share of ``k_iters`` two-sided power iterations of the
    multinomial head: per iteration one ``X.T @ v`` and one ``X @ u`` over
    the (n, d) features (the (n, m) softmax products are not kernel work)."""
    f, b = power_matvec_call(n, d)
    return 2 * k_iters * f, 2 * k_iters * b


def logistic_epoch(n: int, d: int, m: int, k_iters: int) -> tuple:
    """One DFW-Trace epoch of the multinomial head with ``k_iters`` power
    iterations, softmax taken on the fly from the logits Z = X W.

    Passes over X (n, d): 2 per power iteration, 1 for the update's X u.
    Passes over Z (n, m): 2 per power iteration, read and write in the
    update, 1 for the loss and the gap's <Z, P - H>.
    """
    x, z = n * d, n * m
    nbytes = F32 * ((2 * k_iters + 1) * x + (2 * k_iters + 3) * z)
    flops = 4.0 * k_iters * n * (d + m) + 2.0 * x + 7.0 * z
    return flops, nbytes


def mtls_block_power(n: int, d: int, m: int, k_iters: int, k: int) -> tuple:
    """The kernel share of ``k_iters`` block power iterations of width k on
    the least-squares gradient X.T R: per iteration one k-wide pass over
    each of X (n, d) and R (n, m) in each direction -- not k vector passes."""
    flops = 4.0 * k_iters * n * k * (d + m)
    nbytes = F32 * 2 * k_iters * (n * d + n * m + k * (d + m + 2 * n))
    return flops, nbytes


def mtls_block_epoch(n: int, d: int, m: int, k_iters: int, k: int) -> tuple:
    """One ``block:k`` epoch of multi-task least squares with the closed-form
    line search: the block power iterations, then the update, the line
    search and the loss and gap, which read X twice (X u for the update and
    for the line search) and make 6 passes over (n, m) arrays (R and Y read
    by the update, R written, R and Y read by the line search, R read by the
    loss and the gap)."""
    pf, pb = mtls_block_power(n, d, m, k_iters, k)
    x, r = n * d, n * m
    flops = pf + 4.0 * n * k * (d + m) + 14.0 * r
    nbytes = pb + F32 * (2 * x + 6 * r)
    return flops, nbytes
