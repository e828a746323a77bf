"""The reduction from a trace to busy, idle, kernel time and gaps: on
hand-made events and on the small trace recorded on the chip."""

import os

import pytest

from bench_paths import BENCH

from benchmark.harness import xplane as X

OPS = [("while.1", 0.0, 1.0), ("fusion.1", 0.1, 0.2),
       ("_flash_fwd.3", 0.4, 0.3), ("all-reduce.1", 1.2, 0.3),
       ("fusion.2", 1.3, 0.1), ("copy.1", 2.0, 0.5)]
HOST = [("bench.traced", 0.0, 3.0), ("train.step", 1.4, 1.0),
        ("PjitFunction(f)", 1.5, 0.6)]


def test_union_and_busy():
    assert X.union(OPS) == [(0.0, 1.0), (1.2, 1.5), (2.0, 2.5)]
    assert X.busy_seconds(OPS) == pytest.approx(1.8)
    assert X.busy_seconds([]) == 0.0


def test_self_times_give_a_loop_only_its_own_time():
    own = X.self_times(OPS)
    assert own["while.1"] == pytest.approx(0.5)
    assert own["_flash_fwd.3"] == pytest.approx(0.3)
    assert sum(own.values()) == pytest.approx(X.busy_seconds(OPS))


def test_time_of_matches_by_name():
    assert X.time_of(OPS, r"^_flash_fwd") == (pytest.approx(0.3), 1)
    assert X.time_of(OPS, r"^fusion") == (pytest.approx(0.3), 2)
    assert X.time_of(OPS, r"^nothing") == (0, 0)


def test_gaps_and_their_attribution():
    idle = X.gaps(OPS, 0.0, 3.0)
    assert idle == [(1.0, 1.2), (1.5, 2.0), (2.5, 3.0)]
    by_host = X.attribute_gaps(idle, HOST[1:])
    assert by_host == {"_none_": pytest.approx(0.7),
                       "PjitFunction_f_": pytest.approx(0.5)}


def test_exposed_collective_time():
    assert X.exposed_collective_seconds(OPS) == pytest.approx(0.2)
    assert X.exposed_collective_seconds(OPS[:3]) == 0.0


def test_summary_and_breakdown():
    summary = X.summarize(X.Trace({0: OPS, 1: OPS[:3]}, HOST))
    assert summary.window_s == pytest.approx(3.0) and summary.chips == 2
    assert summary.busy_s == pytest.approx((1.8 + 1.0) / 2)
    top = X.breakdown(summary, top=3)
    assert len(top["device_ops"]) == 3 and top["device_ops"][0][1] >= \
        top["device_ops"][1][1]
    assert top["idle_gaps"][0][0] == "_none_"
    assert summary.gaps_s == pytest.approx([0.2, 0.5, 0.5])


def test_clipping_to_the_traced_window():
    trace = X.Trace({0: OPS}, [("bench.traced", 0.5, 1.0)])
    summary = X.summarize(trace)
    assert summary.window_s == pytest.approx(1.0)
    assert summary.busy_s == pytest.approx(0.5 + 0.3)


def test_no_device_operation_is_an_error():
    with pytest.raises(ValueError):
        X.summarize(X.Trace({}, HOST))
    with pytest.raises(ValueError):
        X.summarize(X.Trace({0: []}, HOST))


def test_round_trip_through_json(tmp_path):
    path = str(tmp_path / "cut.json")
    X.dump(X.Trace({0: OPS}, HOST), path)
    back = X.load_json(path)
    assert back.device_ops[0] == OPS and back.host == HOST


RECORDED = os.path.join(BENCH, "harness", "recorded_trace.json")


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded trace in this checkout")
def test_recorded_trace_reduces():
    trace = X.load_json(RECORDED)
    summary = X.summarize(trace)
    assert 0 < summary.busy_s <= summary.window_s
    assert sum(summary.ops.values()) == pytest.approx(summary.busy_s,
                                                      rel=1e-6)
    idle = sum(summary.idle_by_host.values())
    assert idle == pytest.approx(summary.window_s - X.busy_seconds(
        summary.events[0]), abs=1e-9)
    assert X.breakdown(summary)["device_ops"]
    # the first 700 device events of one traced step of the one-chip
    # training cell (chip, PR 26): six flash forward calls, one a layer of
    # the scan that the trace shows as ``while``
    assert len(summary.events[0]) == 689      # 11 of 700 have no length
    seconds, calls = X.time_of(summary.events[0], r"^_flash_fwd")
    assert calls == 6 and seconds == pytest.approx(0.003086559, rel=1e-6)
    assert X.time_of(summary.events[0], r"^while")[1] >= 1
    assert summary.busy_s == pytest.approx(0.027824962, rel=1e-6)
