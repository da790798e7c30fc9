"""The trace reduction on a small synthetic trace."""
import pytest

from bench import trace as tr
from bench.trace import Event

MS = 1_000_000  # ns


SPANS = [("scan_pass", 0, 10 * MS), ("transfer", 4 * MS, 6 * MS)]


def planes():
    """A 10 ms window between two markers: two overlapping ops (1-3 ms,
    2-4 ms), one op 6-7 ms. The host is inside a scan pass all along
    (``SPANS``), and inside a transfer from 4 to 6 ms."""
    return {
        "/device:TPU:0": {
            "XLA Ops": [Event("%kernel custom_call_target=\"tpu_custom_call\"",
                              1 * MS, 2 * MS),
                        Event("%fusion.1", 2 * MS, 2 * MS),
                        Event("%fusion.1", 6 * MS, 1 * MS),
                        Event("%outside", 20 * MS, 1 * MS)],
            "XLA Modules": [Event("jit_bench_mark(1)", -1 * MS, 1 * MS),
                            Event("jit_run", 0, 30 * MS),
                            Event("jit_bench_mark(1)", 10 * MS, 1 * MS)]},
        "/host:CPU": {"python3": [Event("np.asarray", 0, 10 * MS)]},
    }


def test_busy_union_and_idle_share():
    red = tr.reduce(planes(), SPANS)
    assert red["window_s"] == pytest.approx(0.010)
    # union of [1,4] and [6,7] ms; the op outside the window is dropped
    assert red["busy_s"] == pytest.approx(0.004)
    assert 1 - red["busy_s"] / red["window_s"] == pytest.approx(0.6)
    assert red["devices"] == 1


def test_per_op_time_and_calls():
    red = tr.reduce(planes(), SPANS)
    assert red["op_s"]["%fusion.1"] == pytest.approx(0.003)
    assert red["op_n"]["%fusion.1"] == 2
    assert "%outside" not in red["op_s"]
    secs, calls = tr.ops_matching(red, "tpu_custom_call")
    assert (secs, calls) == (pytest.approx(0.002), 1)


def test_gaps_labelled_by_host_span():
    red = tr.reduce(planes(), SPANS)
    # 0-1 and 7-10 ms: the pass alone; 4-6 ms: the pass in a transfer
    assert red["gaps_s"]["scan_pass"] == pytest.approx(0.004)
    assert red["gaps_s"]["transfer"] == pytest.approx(0.002)
    bd = tr.breakdown(red)
    assert bd["idle_gaps"][0] == ["scan_pass", pytest.approx(0.004)]
    assert bd["device_ops"][0][0] == "%fusion.1"


def test_short_gaps_pooled():
    p = planes()
    p["/device:TPU:0"]["XLA Ops"].append(
        Event("%tiny", 7 * MS + 5_000, 5_000))
    red = tr.reduce(p, SPANS)
    assert red["gaps_s"]["short gaps"] == pytest.approx(5e-6)


def test_union_merges_and_skips_empty():
    assert tr.union([(3, 4), (0, 2), (1, 3), (5, 5)]) == [(0, 4)]


def test_window_needs_two_markers():
    p = planes()
    p["/device:TPU:0"]["XLA Modules"].pop()
    with pytest.raises(ValueError):
        tr.reduce(p, SPANS)


def test_host_offset_puts_host_spans_on_the_trace_clock():
    # host clock runs 5 s ahead of the trace clock; each marker is seen
    # 0.1 ms late at opening and sent 0.1 ms early at closing
    opened = 5.0 + 0.0001
    closed = 5.0 + 0.010 - 0.0001
    off = tr.host_offset_ns(planes(), opened, closed)
    assert off == pytest.approx(5e9)
