"""bench/trace_reduce.py on hand-made traces with known answers."""
import pytest

from bench import trace_reduce as tr

MS = 1e6  # ns


def _trace():
    # Window 0..100 ms. Device A: ops 10-30 (fusion), 20-40 (kernel
    # power_matvec), all-reduce 50-60 overlapping compute 55-58, idle else.
    # Device B: one op 0-50.
    return {
        "devices": {
            "/device:TPU:0": [["fusion.1", 10 * MS, 30 * MS],
                              ["power_matvec", 20 * MS, 40 * MS],
                              ["all-reduce.3", 50 * MS, 60 * MS],
                              ["fusion.2", 55 * MS, 58 * MS]],
            "/device:TPU:1": [["fusion.1", 0.0, 50 * MS]],
        },
        "host": [["bench.window", 0.0, 100 * MS],
                 ["bench.fit", 0.0, 100 * MS],
                 ["engine.fetch", 60 * MS, 95 * MS]],
    }


def test_busy_is_the_union_of_op_intervals():
    red = tr.reduce(_trace())
    assert red["window_s"] == pytest.approx(0.1)
    # A: 10-40 and 50-60 -> 40 ms; B: 50 ms; mean 45 ms.
    assert red["busy_s"] == pytest.approx(0.045)
    assert red["devices"] == 2


def test_time_by_kernel_name():
    red = tr.reduce(_trace())
    assert tr.op_seconds(red, ("power_matvec",)) == pytest.approx(0.020 / 2)
    # fusion.1 ran 20 ms on A and 50 ms on B.
    assert red["op_s"]["fusion.1"] == pytest.approx(0.035)


def test_all_reduce_and_its_exposed_part():
    red = tr.reduce(_trace())
    assert red["collective_s"] == pytest.approx(0.010 / 2)
    # 10 ms of all-reduce, 3 ms of it overlapped by fusion.2.
    assert red["collective_exposed_s"] == pytest.approx(0.007 / 2)


def test_breakdown_labels_idle_gaps_with_the_innermost_host_annotation():
    red = tr.reduce(_trace())
    gaps = red["breakdown"]["idle_gaps"]
    # Device A is idle 0-10 (bench.fit), 40-50 (bench.fit), 60-100
    # (middle at 80 ms, inside engine.fetch).
    assert gaps[0] == ["engine.fetch", pytest.approx(0.040)]
    assert sorted(g[1] for g in gaps[1:]) == pytest.approx([0.010, 0.010])
    assert {g[0] for g in gaps[1:]} == {"bench.fit"}
    ops = red["breakdown"]["device_ops"]
    assert ops[0][0] == "fusion.1" and len(ops) <= 10


def test_ops_outside_the_window_are_clipped():
    t = _trace()
    t["host"][0] = ["bench.window", 20 * MS, 45 * MS]
    red = tr.reduce(t)
    assert red["window_s"] == pytest.approx(0.025)
    # A busy 20-40 in the window, B 20-45.
    assert red["busy_s"] == pytest.approx((0.020 + 0.025) / 2)


@pytest.mark.parametrize("op", [["psum.14", 50 * MS, 60 * MS],
                                ["custom.7", 50 * MS, 60 * MS, True]])
def test_all_reduce_found_by_its_hlo_name_or_its_stats(op):
    t = _trace()
    t["devices"]["/device:TPU:0"][2] = op
    red = tr.reduce(t)
    assert red["collective_s"] == pytest.approx(0.010 / 2)
    assert red["collective_exposed_s"] == pytest.approx(0.007 / 2)


def test_no_device_ops_reads_nothing():
    assert tr.reduce({"devices": {}, "host": []}) is None
    assert tr.reduce(None) is None


def test_merge():
    assert tr.merge([[5, 6], [1, 3], [2, 4]]) == [[1.0, 4.0], [5.0, 6.0]]
