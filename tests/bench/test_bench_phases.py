"""The phase readers (``fit_setup_ms``, ``fit_compile_ms``, ``fit_compiles``,
``power_device_ms``, ``update_device_ms``), ``bench/scopes.py`` and the
helpers of ``bench/phases.py`` on hand-made contexts and traces with known
answers."""
import json
import re
from pathlib import Path

import pytest

from bench import harness, phases, scopes, trace_reduce

MS = 1e6  # ns


def _fits():
    return [
        {"job": 0, "epochs": 10, "dur_s": 1.0,
         "span_s": {"engine.setup": 0.10, "engine.dispatch": 0.5},
         "counters": {"jit.compiles": 3.0, "jit.trace_s": 0.05,
                      "jit.lower_s": 0.10, "jit.compile_s": 0.15}},
        {"job": 1, "epochs": 12, "dur_s": 1.2, "traced": True,
         "span_s": {"engine.setup": 0.30},
         "counters": {"jit.compiles": 2.0, "jit.trace_s": 0.01,
                      "jit.lower_s": 0.02, "jit.compile_s": 0.03}},
    ]


def _read(name, ctx):
    return harness.reader(name).read(ctx)


def test_fit_setup_ms_is_the_mean_setup_span():
    assert _read("fit_setup_ms", {"fits": _fits()}) == pytest.approx(200.0)


def test_fit_compile_ms_is_the_mean_of_trace_lower_and_compile():
    assert _read("fit_compile_ms", {"fits": _fits()}) == pytest.approx(180.0)


def test_fit_compiles_is_the_mean_count():
    assert _read("fit_compiles", {"fits": _fits()}) == pytest.approx(2.5)


def test_power_device_ms_is_the_scope_time_per_traced_epoch():
    ctx = {"fits": _fits(), "trace": {"scope_s": {"dfw.power": 0.6}}}
    assert _read("power_device_ms", ctx) == pytest.approx(50.0)


def test_update_device_ms_adds_the_line_search_and_update_scopes():
    ctx = {"fits": _fits(),
           "trace": {"scope_s": {"dfw.update": 0.09, "dfw.linesearch": 0.03}}}
    assert _read("update_device_ms", ctx) == pytest.approx(10.0)


@pytest.mark.parametrize("name", phases.NEW)
def test_a_program_without_the_spans_counters_or_scopes_reads_nothing(name):
    """Against a program that records none of what these read (the parent of
    the change that added them), each reader returns None and never raises."""
    fits = [{"job": 0, "epochs": 10, "dur_s": 1.0, "traced": True,
             "spans_s": 0.9}]
    ctx = {"fits": fits, "trace": {"busy_s": 0.5, "window_s": 1.0}}
    assert _read(name, ctx) is None
    assert _read(name, {}) is None


def _trace():
    # Window 0..100 ms on two devices. Device A: a scan (while) 0-90 ms
    # with a power loop (while, under dfw.power) 10-50 nesting a matvec
    # 20-40, an update fusion 60-70 and an exchange 50-55 under dfw.power;
    # an unscoped op 80-85. Device B: power 0-30 only.
    return {
        "devices": {
            "/device:TPU:0": [["while.8", 0.0, 90 * MS],
                              ["while.7", 10 * MS, 50 * MS],
                              ["matvec.4", 20 * MS, 40 * MS],
                              ["all-reduce.1", 50 * MS, 55 * MS],
                              ["fusion.3", 60 * MS, 70 * MS],
                              ["fusion.9", 80 * MS, 85 * MS]],
            "/device:TPU:1": [["fusion.1", 0.0, 30 * MS]],
        },
        "host": [["bench.window", 0.0, 100 * MS]],
        "scopes": [["/device:TPU:0", "dfw.power", 10 * MS, 50 * MS],
                   ["/device:TPU:0", "dfw.power", 20 * MS, 40 * MS],
                   ["/device:TPU:0", "dfw.power/comm.exchange", 50 * MS, 55 * MS],
                   ["/device:TPU:0", "dfw.update", 60 * MS, 70 * MS],
                   ["/device:TPU:1", "dfw.power", 0.0, 30 * MS]],
    }


def test_scope_seconds_counts_nested_ops_once_and_averages_devices():
    got = scopes.scope_seconds(_trace())
    # dfw.power: A 10-55 = 45 ms (the matvec inside the loop once), B 30 ms
    assert got["dfw.power"] == pytest.approx((0.045 + 0.030) / 2)
    assert got["comm.exchange"] == pytest.approx(0.005 / 2)
    assert got["dfw.update"] == pytest.approx(0.010 / 2)
    assert "dfw.loss" not in got


def test_scope_seconds_is_clipped_to_the_window_and_empty_without_scopes():
    t = _trace()
    t["host"][0] = ["bench.window", 30 * MS, 100 * MS]
    assert scopes.scope_seconds(t)["dfw.power"] == pytest.approx(0.025 / 2)
    assert scopes.scope_seconds({"devices": t["devices"], "host": t["host"]}) == {}


@pytest.mark.parametrize("text,want", [
    ("jit(step)/jit(main)/while/body/dfw.power/comm.exchange/psum",
     ("dfw.power", "comm.exchange")),
    ("jit(step)/while/body/cond/branch_1_fun/dfw.update/mul", ("dfw.update",)),
    ("jit(step)/while/body/dfw.powered/add", ()),
    ("fusion.3", ()),
])
def test_scopes_are_read_from_the_name_stack(text, want):
    assert scopes.scopes_of(text) == want
    key, found = scopes.event_scopes([("hlo_category", "fusion"), ("tf_op", text),
                                      ("flops", 12)])
    assert found == want and key == ("tf_op" if want else None)


def test_scan_coverage_is_the_scoped_share_of_the_outermost_loop():
    cov = phases.scan_coverage(_trace())
    # scan 0-90; scoped 10-55 and 60-70 -> 55 of 90 ms
    assert cov["scans"] == 1
    assert cov["scan_s"] == pytest.approx(0.090)
    assert cov["share"] == pytest.approx(55 / 90)


def test_keep_trace_starts_at_the_first_scoped_op():
    t = _trace()
    t["scopes"] = t["scopes"][:4]  # first scoped op: device A at 10 ms
    kept = phases.keep_trace(t, keep_ops=2)
    # the scan encloses the start; then the two ops from 10 ms on
    assert [o[0] for o in kept["devices"]["/device:TPU:0"]] == [
        "while.8", "while.7", "matvec.4"]
    assert "/device:TPU:1" not in kept["devices"]  # nothing from 10 ms on
    assert len(kept["scopes"]) == 4 and kept["host"]  # all inside the scan


def _key(field, wire):
    return _varint((field << 3) | wire)


def _varint(n):
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _int(field, n):
    return _key(field, 0) + _varint(n)


def _msg(field, payload):
    if isinstance(payload, str):
        payload = payload.encode()
    return _key(field, 2) + _varint(len(payload)) + payload


def _xspace():
    """A serialized XSpace with one TPU plane: stat metadata 1 ``tf_op`` and
    2 the interned name stack; event metadata 10 (a matvec whose ``tf_op``
    refers to the name stack), 11 (an update fusion with the stack as a
    string) and 12 (no scope); events on the ``XLA Ops`` line."""
    stack = "jit(step)/while/body/dfw.power/matvec"
    stat_meta = (_msg(5, _int(1, 1) + _msg(2, _int(1, 1) + _msg(2, "tf_op")))
                 + _msg(5, _int(1, 2) + _msg(2, _int(1, 2) + _msg(2, stack))))
    ev_meta = b""
    for mid, name, stat in (
            (10, "%matvec.4 = f32[8] custom-call()", _int(1, 1) + _int(7, 2)),
            (11, "%fusion.3 = f32[8] fusion()",
             _int(1, 1) + _msg(5, "jit(step)/while/body/dfw.update/mul")),
            (12, "%fusion.9 = f32[8] fusion()", _int(1, 1) + _msg(5, "jit(step)/add"))):
        meta = _int(1, mid) + _msg(2, name) + _msg(4, name.split()[0][1:]) + _msg(5, stat)
        ev_meta += _msg(4, _int(1, mid) + _msg(2, meta))
    events = b"".join(
        _msg(4, _int(1, mid) + _int(2, int(start * 1e9)) + _int(3, int(dur * 1e9)))
        for mid, start, dur in ((10, 1.0, 2.0), (11, 4.0, 1.0), (12, 6.0, 1.0)))  # ms
    line = _int(1, 1) + _msg(2, "XLA Ops") + _int(3, 1000) + events
    plane = _int(1, 7) + _msg(2, "/device:TPU:0") + _msg(3, line) + ev_meta + stat_meta
    return _msg(1, plane)


def test_scopes_are_read_from_the_event_metadata_of_a_serialized_trace(tmp_path):
    meta = scopes.metadata_scopes(_xspace())
    events = meta["/device:TPU:0"]["events"]
    assert events["%matvec.4 = f32[8] custom-call()"] == ("tf_op", ("dfw.power",))
    assert events["fusion.3"] == ("tf_op", ("dfw.update",))
    assert "fusion.9" not in events
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_xspace())
    got = scopes.load(str(path))
    assert got["scope_stat"] == "tf_op"
    assert [r[1] for r in got["scopes"]] == ["dfw.power", "dfw.update"]
    start, end = got["scopes"][0][2:]
    assert end - start == pytest.approx(2 * MS)
    assert got["lines"]["/device:TPU:0"] == ["XLA Ops"]


# One epoch of the head cell's traced job, recorded on a TPU v5e by
# bench/phases.py (its "recorded" key says how): the device operations from
# the start of the scan, the host annotations over them, and the scope rows.
RECORDED = Path(__file__).with_name("trace_head_in1k_fit_1chip_scopes.json")


@pytest.fixture(scope="module")
def recorded():
    return json.loads(RECORDED.read_text())


def test_the_chip_keeps_the_name_stack_in_the_tf_op_stat_of_the_metadata(recorded):
    dev = "/device:TPU:0"
    assert recorded["scope_stat"] == "tf_op"
    assert "tf_op" in recorded["metadata_stats"][dev]
    assert "XLA Ops" in recorded["lines"][dev]


def test_a_recorded_epoch_splits_into_loss_power_and_update(recorded):
    got = scopes.scope_seconds(recorded)
    assert set(got) == {"dfw.loss", "dfw.power", "dfw.update"}
    assert got["dfw.power"] > got["dfw.loss"] > got["dfw.update"] > 0
    # the conditional that runs the epoch inside the scan is covered
    ops = recorded["devices"]["/device:TPU:0"]
    epoch = next(o for o in ops if o[0].startswith("%conditional"))
    assert sum(got.values()) >= 0.95 * (epoch[2] - epoch[1]) / 1e9


def test_recorded_kernels_match_the_roofline_reader_and_lie_under_dfw_power(recorded):
    reader = harness.reader("power_matvec_roofline")
    ops = recorded["devices"]["/device:TPU:0"]
    kernels = [o for o in ops if re.match(r"%r?matvec\.\d+ = ", o[0])]
    assert len(kernels) == 4  # K = 2 power iterations: X v and X^T u each
    assert all("custom-call(" in o[0] for o in kernels)
    # The reader matches names by substring, so it also counts the
    # operations that take a kernel's result as an operand (1.4 % more
    # time in this epoch than the kernels alone).
    consumers = [o for o in ops if o not in kernels
                 and any(p in o[0] for p in reader.KERNEL)]
    assert consumers
    red = trace_reduce.reduce(recorded)
    assert trace_reduce.op_seconds(red, reader.KERNEL) == pytest.approx(
        sum(o[2] - o[1] for o in kernels + consumers) / 1e9)
    power = [(s, e) for _, names, s, e in recorded["scopes"]
             if "dfw.power" in names.split("/")]
    assert all(any(a <= o[1] and o[2] <= b for a, b in power) for o in kernels)
