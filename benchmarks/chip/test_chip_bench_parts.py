"""CPU tests of the chip benchmark's parts: discovery by name, the FLOPs
count, the HLO transfer count, the trace reduction on a recorded trace, and
the comparison's arithmetic."""
import dataclasses
import json
import math
import os

import pytest

import devtrace
import flops
import harness
import hlo
import oracle

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = json.load(open(os.path.join(harness.ROOT, "BENCHMARK.json")))
FIXTURE = os.path.join(HERE, "fixtures", "olmo-1b.train.s2048b4.trace.json")


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_resolves_by_name(cell):
    c = harness.load_cell(cell)
    assert c["traffic"]["chips"] == c["chips"]
    assert set(c["checks"]["limits"]) == set(oracle.NUMBERS)
    assert "setup_s" in c["end_to_end"] and len(c["end_to_end"]) >= 2
    assert c["per_layer"]
    for name in c["end_to_end"] + c["per_layer"]:
        assert callable(harness.metric_reader(name))
    ref = harness.reference_module(c["config"]["reference"])
    assert hasattr(ref, "Reference")


@pytest.mark.parametrize("conf", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file_differs_from_the_repo_config_only_where_reduced(conf):
    from repro.configs import get_config
    f = json.load(open(os.path.join(harness.ROOT, conf["file"])))
    assert f["name"] == conf["name"] and f["reduced"] == conf["reduced"]
    base = dataclasses.asdict(get_config(f["arch"]))
    changed = {k for k, v in f["model"].items() if base[k] != v}
    assert changed <= set(conf["reduced"])
    assert set(f["reduced"]) <= changed


def test_metric_names_resolve_to_readers_and_moves_is_end_to_end():
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert callable(harness.metric_reader(m["name"]))


def test_flops_per_token_olmo_1b_by_hand():
    m = json.load(open(os.path.join(HERE, "configs", "olmo-1b.json")))["model"]
    d, f, layers, v, s = 2048, 8192, 16, 50304, 2048
    by_hand = (6 * layers * (4 * d * d + 3 * d * f)    # q, k, v, o; SwiGLU
               + 6 * d * v                              # tied head
               + 6 * layers * s * 16 * 128)             # causal QK^T, PV
    assert flops.flops_per_token(m, s) == by_hand
    assert abs(by_hand - 7.4625e9) / 7.4625e9 < 1e-3


def test_flops_counts_biases_and_untied_head():
    m = json.load(open(os.path.join(HERE, "configs",
                                    "starcoder2-7b-l4.json")))["model"]
    d, k, f = 4608, 512, 18432
    per_layer = (2 * d * d + 2 * d * k + 2 * d * f          # weights
                 + (d + 2 * k) + d + f + d                 # biases
                 + 2 * 2 * d)                              # two LayerNorms
    n = 4 * per_layer + 2 * d
    assert flops.flops_per_token(m, 4096) == (6 * n + 6 * d * 49152
                                              + 6 * 4 * 4096 * 36 * 128)


# instruction texts as the TPU trace names the operations (olmo-1b and
# starcoder2-7b-l4 steps on a v5e)
DS_START = ("%dynamic-slice-start.21 = ((f32[50304,2048]{1,0:T(8,128)S(5)}, "
            "s32[]{:T(128)}, s32[]{:T(128)}), f32[3144,2048]{1,0:T(8,128)}, "
            "u32[]{:S(2)}) async-start(f32[50304,2048]{1,0:T(8,128)S(5)} "
            "%get-tuple-element.5103, s32[]{:T(128)} %select_n.492, s32[]"
            "{:T(128)} %constant.337..sunk.1), calls=%async_computation.42")
DS_DONE = ("%dynamic-slice-done.21 = f32[3144,2048]{1,0:T(8,128)} async-done(("
           "(f32[50304,2048]{1,0:T(8,128)S(5)}, s32[]{:T(128)}, s32[]{:T(128)"
           "}), f32[3144,2048]{1,0:T(8,128)}, u32[]{:S(2)}) "
           "%dynamic-slice-start.21)")
DUS_START = ("%dynamic-update-slice-start.21 = ((f32[50304,2048]{1,0:T(8,128)"
             "S(5)}, f32[3144,2048]{1,0:T(8,128)}, s32[]{:T(128)}, s32[]{:T("
             "128)}), f32[50304,2048]{1,0:T(8,128)S(5)}, u32[]{:S(2)}) "
             "async-start(f32[50304,2048]{1,0:T(8,128)S(5)} %get-tuple-"
             "element.5101, f32[3144,2048]{1,0:T(8,128)} %get-tuple-element."
             "4418, s32[]{:T(128)} %select_n.492, s32[]{:T(128)} %constant."
             "337..sunk.1), calls=%async_computation")
DUS_DONE = ("%dynamic-update-slice-done.21 = f32[50304,2048]{1,0:T(8,128)S(5)}"
            " async-done(((f32[50304,2048]{1,0:T(8,128)S(5)}, f32[3144,2048]"
            "{1,0:T(8,128)}, s32[]{:T(128)}, s32[]{:T(128)}), f32[50304,2048]"
            "{1,0:T(8,128)S(5)}, u32[]{:S(2)}) %dynamic-update-slice-start.21)")
COPY_START = ("%copy-start = (f32[4608]{0:T(1024)}, f32[4608]{0:T(1024)S(5)}, "
              "u32[]{:S(2)}) copy-start(f32[4608]{0:T(1024)S(5)} %state_opt_"
              "master__final_norm____bias__.1)")
COPY_DONE = ("%copy-done = f32[4608]{0:T(1024)} copy-done((f32[4608]{0:T("
             "1024)}, f32[4608]{0:T(1024)S(5)}, u32[]{:S(2)}) %copy-start)")
WHILE = ("%while.126 = (s32[]{:T(128)}, bf16[4,1,4096,4608]{3,2,1,0:T(8,128)"
         "(2,1)S(5)}) while((s32[]{:T(128)}, bf16[4,1,4096,4608]{3,2,1,0:T("
         "8,128)(2,1)S(5)}) %tuple.9), condition=%cond, body=%body")


@pytest.mark.parametrize("text,want", [
    (DS_START, ("start", "to_device", 3144 * 2048 * 4)),
    (DS_DONE, ("done", "to_device", 3144 * 2048 * 4)),
    (DUS_START, ("start", "to_host", 3144 * 2048 * 4)),
    (DUS_DONE, ("done", "to_host", 3144 * 2048 * 4)),
    (COPY_START, ("start", "to_device", 4608 * 4)),
    (COPY_DONE, ("done", "to_device", 4608 * 4)),
    (WHILE, None),
], ids=["ds_start", "ds_done", "dus_start", "dus_done", "copy_start",
        "copy_done", "while"])
def test_host_transfers_are_recognised_from_the_instruction(text, want):
    assert hlo.transfer(text) == want


def test_short_name():
    assert hlo.short_name(DS_DONE) == "dynamic-slice-done.21 async-done"


def test_union_and_gaps_against_brute_force():
    iv = [(0, 3), (2, 5), (7, 8), (7.5, 9), (12, 13)]
    covered = sum(1 for x in range(0, 1400)
                  if any(s <= x / 100 < e for s, e in iv)) / 100
    assert devtrace.union_ns(iv) == pytest.approx(covered, abs=0.02)
    assert devtrace.idle_gaps(iv, 0, 14) == [(5, 7), (9, 12), (13, 14)]


def _fixture_ctx():
    data = json.load(open(FIXTURE))
    for plane in data["trace"]["devices"].values():
        plane["ops"] = [[data["names"][i], s, e] for i, s, e in plane["ops"]]
    import types
    return types.SimpleNamespace(
        trace=data["trace"], window_s=data["window_s"],
        tokens_per_step=8192, chips=1, peak={"bf16_flops_per_s": 197e12},
        flops_per_token=flops.flops_per_token(
            json.load(open(os.path.join(HERE, "configs",
                                        "olmo-1b.json")))["model"], 2048)), data


def test_trace_reduction_on_a_recorded_olmo_trace():
    ctx, data = _fixture_ctx()
    read = {n: harness.metric_reader(n)(ctx) for n in
            ("host_gap_ms", "mfu", "swap_wait_ms", "swap_gb", "idle_frac")}
    plane = sorted(ctx.trace["devices"])[0]
    module = devtrace.step_module(ctx.trace)
    runs = devtrace.step_runs(ctx.trace, plane, module)
    assert len(runs) == data["steps"]
    gaps = [b[0] - a[1] for a, b in zip(runs, runs[1:])]
    assert read["host_gap_ms"] == pytest.approx(sum(gaps) / len(gaps) / 1e6)
    span = (runs[-1][1] - runs[0][0]) / 1e9
    assert read["mfu"] == pytest.approx(
        100 * ctx.flops_per_token * len(runs) * 8192 / span / 197e12)
    # every host transfer of the optimizer sweep completed once per slice:
    # the bytes are the plan's 28.24 GB a step
    assert read["swap_gb"] == pytest.approx(data["plan_swap_gb"], rel=1e-6)
    assert 0 < read["swap_wait_ms"] < 1e3 * span / len(runs)
    assert 0 <= read["idle_frac"] < 100


def test_norm_gap_is_relative_to_the_larger_of_leaf_and_median():
    ref = {"a": 1.0, "b": 2.0, "c": 3.0, "tiny": 1e-6}
    prog = dict(ref, tiny=2e-6, c=3.3)
    value, leaf = oracle.norm_gap(prog, ref)
    # median of the reference is 1.5: the tiny leaf's gap counts against it
    assert leaf == "c" and value == pytest.approx(0.1)
    assert oracle.norm_gap({"a": 1.0}, {"b": 1.0})[0] == math.inf
    assert oracle.moving_leaves(ref) == ["a", "b", "c"]
    assert oracle.loss_gap([1.0, 2.0], [1.0, 2.5]) == 0.5
    assert oracle.loss_gap([1.0, float("nan")], [1.0, 2.0]) == math.inf
