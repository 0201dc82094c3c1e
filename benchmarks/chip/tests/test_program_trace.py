"""The reduction of the program's own names in a trace
(``program_trace.py``) and the readers of the per-stage metrics, on a
trace recorded on a TPU v5 lite chip (``program_trace.json``: three
periods of ``port_offpeak`` with the stage scopes and ``serve/*`` spans,
as ``program_trace.read_xplane`` returns them, cut to the window, with
the window's summed ``reports_due`` and ``reports_sent``), on the older
``recorded_trace.json``, which predates those names, and on a small
trace written out here. Run by path:

    python -m pytest benchmarks/chip/tests/test_program_trace.py
"""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import program_trace  # noqa: E402
import run  # noqa: E402
import trace_reduce  # noqa: E402


def load(name):
    with open(os.path.join(HERE, "tests", name)) as f:
        return json.load(f)


NEW = load("program_trace.json")
OLD = load("recorded_trace.json")
READERS = {
    "reporter_ms", "translate_ms", "collector_ms", "enrich_ms",
    "host_replay_ms", "host_upload_ms", "batch_wait_ms",
    "deferred_report_share"}
STAGES = ("reporter/ingest", "reporter/due", "reporter/reports", "route",
          "translate", "collector", "collector/validate", "collector/place",
          "enrich")


def reader(name):
    return run.load_module(os.path.join(HERE, "layers", name + ".py"),
                           "layer_" + name)


def window(raw):
    (w,) = [h for h in raw["host"] if h[0] == "window"]
    return w[1], w[1] + w[2]


def clipped(s, d, w0, w1):
    return max(0, min(s + d, w1) - max(s, w0))


def ctx(rec, red):
    return {"trace": red, "periods": rec["periods"],
            "reports_due": rec.get("reports_due"),
            "reports_sent": rec.get("reports_sent")}


def test_stage_path():
    sp = program_trace.stage_path
    assert sp("jit(dfa_step)/shard_map/reporter/vmap(due)/top_k") == \
        "reporter/due"
    assert sp("jit(dfa_step)/collector/place/pallas_call") == \
        "collector/place"
    assert sp("jit(dfa_step)/collector/pmax") == "collector"
    assert sp("jit(dfa_step)/enrich/infer/dot_general") == "enrich/infer"
    assert sp("jit(dfa_step)/sub") is None
    assert sp(None) is None


def test_stage_time_is_the_sum_of_its_scoped_clipped_ops():
    raw = NEW["raw"]
    red = program_trace.reduce(raw)
    w0, w1 = window(raw)
    (dev,) = raw["devices"]
    ops, scopes = raw["devices"][dev], raw["scopes"][dev]
    assert len(ops) == len(scopes)
    want = {}
    for (_, s, d), st in zip(ops, scopes):
        want[st] = want.get(st, 0) + clipped(s, d, w0, w1)
    unscoped = want.pop(None, 0)
    assert set(STAGES) <= set(red["stage_ns"])
    assert red["stage_ns"] == want
    assert red["unscoped_ns"] == unscoped
    total = sum(clipped(s, d, w0, w1) for _, s, d in ops)
    assert sum(red["stage_ns"].values()) + red["unscoped_ns"] == total
    # the kernels sit in their stages
    kernel_stage = {"ingest_update": "reporter/ingest",
                    "ring_scatter": "collector/place",
                    "gather_enrich": "enrich"}
    for (name, _, _), st in zip(ops, scopes):
        for prefix, stage in kernel_stage.items():
            if name.startswith(prefix):
                assert st == stage, name


def test_stage_readers():
    raw = NEW["raw"]
    red = program_trace.reduce(raw)
    n = NEW["periods"]
    got = {m: reader(m).read(ctx(NEW, red)) for m in READERS}

    def ms(*tops):
        return sum(v for k, v in red["stage_ns"].items()
                   if k.split("/")[0] in tops) / n / 1e6
    assert got["reporter_ms"] == pytest.approx(ms("reporter"))
    assert got["translate_ms"] == pytest.approx(
        ms("route", "exchange", "translate"))
    assert got["collector_ms"] == pytest.approx(ms("collector"))
    assert got["enrich_ms"] == pytest.approx(ms("enrich"))
    assert got["collector_ms"] >= trace_reduce.kernel_ns(
        red, "ring_scatter") / n / 1e6
    assert got["deferred_report_share"] == pytest.approx(
        100 * (NEW["reports_due"] - NEW["reports_sent"])
        / NEW["reports_due"])


def test_serving_phases_per_period():
    raw = NEW["raw"]
    red = program_trace.reduce(raw)
    w0, w1 = window(raw)
    serve = [x for x in raw["serve"] if w0 <= x[1] < w1]
    periods = {k for _, _, _, k in serve}
    assert periods == set(range(NEW["periods"]))
    for phase in ("next_batch", "stage", "dispatch", "wait"):
        mine = [d for n, _, d, _ in serve if n == "serve/" + phase]
        assert len(mine) == NEW["periods"], phase
        assert red["phase_ns"][phase] == sum(mine) / len(periods)
    end = {k: s + d for n, s, d, k in serve if n == "serve/stage"}
    waits = [s - end[k] for n, s, _, k in serve
             if n == "serve/dispatch" and k > 0]
    assert len(waits) == NEW["periods"] - 1
    assert red["batch_wait_ns"] == sum(waits) / len(waits)
    got = {m: reader(m).read(ctx(NEW, red)) for m in READERS}
    assert got["host_replay_ms"] == red["phase_ns"]["next_batch"] / 1e6
    assert got["host_upload_ms"] == red["phase_ns"]["stage"] / 1e6
    assert got["batch_wait_ms"] == red["batch_wait_ns"] / 1e6
    # a period's batch waits while the previous step runs
    assert got["batch_wait_ms"] > got["host_upload_ms"]


def test_gaps_take_the_program_span_label():
    red = program_trace.reduce(NEW["raw"])
    labels = {k for k, _ in red["idle_gaps"]}
    assert labels & {"serve/next_batch", "serve/wait", "serve/dispatch",
                     "serve/stage"}
    idle = sum(v for _, v in red["idle_gaps"]) * 1e9
    assert idle == pytest.approx(red["window_ns"] - red["busy_ns"], abs=10)


def test_gaps_fall_back_to_the_benchmark_spans():
    # window [0, 100]; device busy [0,10], [40,50], [90,100]: gaps
    # [10,40] under serve/wait (and the benchmark's dispatch, which
    # overlaps it more) and [50,90] under the benchmark's next_batch only
    raw = {"devices": {"0": [["a", 0, 10], ["b", 40, 10], ["c", 90, 10]]},
           "scopes": {"0": ["collector/place", None, "enrich"]},
           "host": [["window", 0, 100], ["dispatch", 10, 30],
                    ["next_batch", 50, 40]],
           "serve": [["serve/wait", 12, 26, 0]]}
    red = program_trace.reduce(raw)
    assert dict(red["idle_gaps"]) == pytest.approx(
        {"serve/wait": 30e-9, "next_batch": 40e-9})
    assert red["stage_ns"] == {"collector/place": 10, "enrich": 10}
    assert red["unscoped_ns"] == 10
    assert dict(red["device_ops"]) == pytest.approx(
        {"a (collector/place)": 10e-9, "b": 10e-9, "c (enrich)": 10e-9})
    without = {**raw, "serve": []}
    assert program_trace.reduce(without)["idle_gaps"] == \
        trace_reduce.reduce(without)["idle_gaps"]


@pytest.mark.parametrize("rec", [NEW, OLD], ids=["new", "old"])
def test_the_benchmarks_own_numbers_stay(rec):
    raw = rec["raw"]
    old = trace_reduce.reduce(raw)
    new = program_trace.reduce(raw)
    for k in ("window_ns", "busy_ns", "n_devices", "op_ns"):
        assert new[k] == old[k], k
    assert [v for _, v in new["device_ops"]] == \
        [v for _, v in old["device_ops"]]
    assert [k.split(" ")[0] for k, _ in new["device_ops"]] == \
        [k for k, _ in old["device_ops"]]


def test_the_old_trace_reduces_to_identical_values():
    old = trace_reduce.reduce(OLD["raw"])
    new = program_trace.reduce(OLD["raw"])
    assert {k: new[k] for k in old} == old
    assert new["stage_ns"] == {} and new["phase_ns"] == {}
    assert new["unscoped_ns"] == 0 and new["batch_wait_ns"] is None


def test_new_readers_read_nothing_without_the_programs_names():
    for red in (trace_reduce.reduce(OLD["raw"]),
                program_trace.reduce(OLD["raw"]), None):
        for m in READERS:
            assert reader(m).read(ctx(OLD, red)) is None, m
    # the counter: absent on an older program, or no flow due
    for due, sent in ((None, None), (0, 0)):
        assert reader("deferred_report_share").read(
            {"reports_due": due, "reports_sent": sent}) is None
