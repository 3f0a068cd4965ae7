"""CPU tests of the reader of the program's own spans (`feed_ms`): on a
hand-made trace, on a recorded olmo trace with the program's spans, and on
the older recording, which predates them."""
import json
import os
import types

import pytest

import harness

HERE = os.path.dirname(os.path.abspath(__file__))
OLD_FIXTURE = os.path.join(HERE, "fixtures",
                           "olmo-1b.train.s2048b4.trace.json")
SCOPED_FIXTURE = os.path.join(HERE, "fixtures",
                              "olmo-1b.train.s2048b4.scoped.trace.json")


def _ctx(trace):
    return types.SimpleNamespace(trace=trace, window_s=1.0)


def test_feed_ms_by_hand():
    # three executions of the step program (and a shorter program that is
    # not the step); data.fetch spans before the first step start, between
    # the starts, and after the last start
    trace = {"devices": {"/device:TPU:0": {"ops": [], "modules": [
        ["jit_step", 100e6, 300e6], ["jit_small", 305e6, 306e6],
        ["jit_step", 320e6, 520e6], ["jit_step", 540e6, 740e6]]}},
        "host": [["data.fetch", 90e6, 99e6],        # before the first start
                 ["data.fetch", 302e6, 314e6],       # 12 ms
                 ["bench.feed", 303e6, 313e6],
                 ["data.fetch", 522e6, 536e6],       # 14 ms
                 ["train.put", 536e6, 538e6],
                 ["data.fetch", 742e6, 800e6]]}      # after the last start
    assert harness.metric_reader("feed_ms")(_ctx(trace)) == \
        pytest.approx((12 + 14) / 2)


def test_feed_ms_on_a_recorded_trace_with_program_spans():
    # two steps on one v5e: the data.fetch of the first step's batch runs
    # before the first step program starts; the one between the two starts
    # runs from 2464454882 to 2472360242 ns
    trace = json.load(open(SCOPED_FIXTURE))["trace"]
    fetches = [(s, e) for name, s, e in trace["host"] if name == "data.fetch"]
    assert fetches == [(46736728.0, 54521828.0),
                       (2464454882.0, 2472360242.0)]
    assert harness.metric_reader("feed_ms")(_ctx(trace)) == \
        pytest.approx((2472360242 - 2464454882) / 1e6)


def test_feed_ms_is_none_without_program_spans():
    # the recorded olmo trace holds only the benchmark's own annotations
    data = json.load(open(OLD_FIXTURE))
    trace = data["trace"]
    assert not any(name == "data.fetch" for name, _, _ in trace["host"])
    assert harness.metric_reader("feed_ms")(_ctx(trace)) is None
    # and a trace with one step program has no interval to average over
    one = {"devices": {"/device:TPU:0": {"ops": [], "modules": [
        ["jit_step", 0.0, 1e6]]}}, "host": [["data.fetch", 0.0, 5e5]]}
    assert harness.metric_reader("feed_ms")(_ctx(one)) is None
