"""The reduction from trace to numbers, on a small recorded trace kept in
the plain form (``data/trace_small.json``) and on hand-made intervals."""

import json
import os

from benchmark import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))


def test_merge_is_a_union():
    assert trace_reduce.merge([(5, 7), (0, 2), (1, 3), (7, 8)]) == [
        (0, 3), (5, 8)]
    assert trace_reduce.merge([(0, 10), (2, 3)]) == [(0, 10)]


def test_busy_ops_and_gaps_of_hand_made_intervals():
    trace = {"device": {"/device:TPU:0": [
        ["fusion.1", 0.0, 2e9], ["fusion.2", 1e9, 2e9],      # overlap: 3 s
        ["copy", 5e9, 1e9], ["fusion.1", 8e9, 1e9]]},
        "host": [["step", 0.0, 4.5e9], ["input_wait", 3e9, 1.2e9],
                 ["fetch", 6.5e9, 1e9]]}
    out = trace_reduce.reduce(trace, window_s=10.0)
    assert out["busy_s"] == 5.0
    assert out["device_ops"][0] == ["fusion.1", 3.0]
    gaps = dict(map(tuple, out["idle_gaps"]))
    # gap 3-5 s: middle at 4 s lies in both spans, the shorter one owns it
    assert gaps == {"input_wait": 2.0, "fetch": 2.0}


def test_an_enclosing_operation_counts_without_what_it_encloses():
    events = [["%while.1 = (s32[]) while(...)", 0.0, 10e9],
              ["%fusion.7 = bf16[8,128]{1,0} fusion(...)", 1e9, 3e9],
              ["%fusion.7 = bf16[8,128]{1,0} fusion(...)", 5e9, 3e9]]
    own = trace_reduce.self_seconds(events)
    assert own == {"while.1 (s32[])": 4.0, "fusion.7 bf16[8,128]": 6.0}


def test_everything_is_clipped_to_the_window_span():
    trace = {"device": {"/device:TPU:0": [
        ["a", 0.0, 3e9], ["b", 4e9, 1e9], ["c", 7e9, 4e9]]},
        "host": [["trace_window", 2e9, 6e9], ["input_wait", 5e9, 2e9]]}
    out = trace_reduce.reduce(trace, window_s=99.0)
    assert out["window_s"] == 6.0
    assert out["busy_s"] == 1.0 + 1.0 + 1.0      # a, b, c inside [2, 8] s
    assert dict(map(tuple, out["idle_gaps"])) == {
        "unattributed": 1.0, "input_wait": 2.0}


def test_recorded_trace():
    with open(os.path.join(HERE, "data", "trace_small.json")) as f:
        rec = json.load(f)
    out = trace_reduce.reduce(rec["trace"], rec["window_s"])
    assert 0 < out["busy_s"] <= out["window_s"]
    assert abs(out["busy_s"] - rec["expect"]["busy_s"]) < 1e-9
    assert out["device_ops"][0][0] == rec["expect"]["top_op"]
    assert len(out["device_ops"]) <= 10 and len(out["idle_gaps"]) <= 10
