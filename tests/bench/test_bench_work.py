"""Operation and byte counts of bench/work.py against hand counts, and the
peak table."""
import pytest

from bench import peaks, work


def test_power_matvec_call():
    # A (3, 5) @ v: 15 multiply-adds; A, v and the result read or written once.
    assert work.power_matvec_call(3, 5) == (30.0, 4 * (15 + 3 + 5))


def test_factor_matvec_batch():
    # b=2 rows, d=4, m=3, rank 2: x @ U.T (2*2*4 MACs), scale (2*2), @ V (2*2*3 MACs).
    flops, nbytes = work.factor_matvec_batch(2, 4, 3, 2)
    assert flops == 2 * 16 + 4 + 2 * 12
    assert nbytes == 4 * (2 * 4 + 2 * 4 + 2 + 2 * 3 + 2 * 3)


def test_logistic_epoch_two_power_iterations():
    n, d, m = 10, 4, 3
    flops, nbytes = work.logistic_epoch(n, d, m, 2)
    # X read 2*2 times by the power iterations and once by the update;
    # Z read 2*2 times, read and written by the update, read by loss and gap.
    assert nbytes == 4 * (5 * n * d + 7 * n * m)
    assert flops == 4 * 2 * n * (d + m) + 2 * n * d + 7 * n * m


def test_logistic_epoch_at_imagenet_width():
    # 327,680 x 2048 features, m = 1000, K = 2: about 22.6 GB, 27.6 ms at 819 GB/s.
    flops, nbytes = work.logistic_epoch(327_680, 2048, 1000, 2)
    assert nbytes == pytest.approx(22.6e9, rel=0.01)
    t, bound = peaks.roofline_s(flops, nbytes, "TPU v5 lite")
    assert bound == "bytes" and t == pytest.approx(27.6e-3, rel=0.01)


def test_logistic_power_is_the_kernel_passes_over_x():
    assert work.logistic_power(10, 4, 3, 2) == (
        4 * work.power_matvec_call(10, 4)[0], 4 * work.power_matvec_call(10, 4)[1])


def test_mtls_block_is_k_wide_passes_not_k_vector_passes():
    n, d, m, k = 10, 4, 3, 8
    f1, b1 = work.mtls_block_power(n, d, m, 2, 1)
    fk, bk = work.mtls_block_power(n, d, m, 2, k)
    assert fk == k * f1  # operations grow with the width...
    # ...but X and R are read 2 * K times whatever the width.
    assert bk - b1 == 4 * 2 * 2 * (k - 1) * (d + m + 2 * n)
    assert b1 == 4 * 2 * 2 * (n * d + n * m + (d + m + 2 * n))


def test_mtls_block_epoch_adds_update_linesearch_and_loss():
    n, d, m, k = 10, 4, 3, 2
    pf, pb = work.mtls_block_power(n, d, m, 2, k)
    flops, nbytes = work.mtls_block_epoch(n, d, m, 2, k)
    assert nbytes - pb == 4 * (2 * n * d + 6 * n * m)
    assert flops - pf == 4 * n * k * (d + m) + 14 * n * m


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks.roofline_s(1.0, 1.0, "cpu")


def test_roofline_names_its_bound():
    p = peaks.peaks("TPU v5 lite")
    assert peaks.roofline_s(p.flops_per_s, 1.0, "TPU v5 lite") == (1.0, "flops")
    assert peaks.roofline_s(1.0, p.hbm_bytes_per_s, "TPU v5 lite") == (1.0, "bytes")
