"""The reduction from a profiler trace to the per-layer metrics, and the
roofline byte counts, on a small trace recorded on a TPU v5 lite chip
(``recorded_trace.json``: three periods of ``port_offpeak`` as
``trace_reduce.read_xplane`` returns them, with the reference's work
counts for those periods). Run by path:

    python -m pytest benchmarks/chip/tests/test_trace_reduction.py
"""
import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import run  # noqa: E402
import trace_reduce  # noqa: E402
import work  # noqa: E402

with open(os.path.join(HERE, "tests", "recorded_trace.json")) as f:
    REC = json.load(f)
PEAKS = run.load_json(HERE, "peaks.json")["devices"]["TPU v5 lite"]


def window():
    (w,) = [h for h in REC["raw"]["host"] if h[0] == "window"]
    return w[1], w[1] + w[2]


def sweep_busy(ops, w0, w1):
    """Busy time by a coverage sweep over the clipped interval edges."""
    edges = []
    for _, s, d in ops:
        s, e = max(s, w0), min(s + d, w1)
        if e > s:
            edges += [(s, 1), (e, -1)]
    edges.sort()
    busy, depth, last = 0, 0, None
    for t, step in edges:
        if depth > 0:
            busy += t - last
        depth += step
        last = t
    return busy


def ctx(red):
    return {"trace": red, "periods": REC["periods"], "work": REC["work"],
            "host_s": REC["host_s"], "peaks": PEAKS, "dfa": REC["dfa"],
            "fv": 3 * 4096, "seq_anomalies": 2048}


def test_window_and_busy():
    red = trace_reduce.reduce(REC["raw"])
    w0, w1 = window()
    assert red["window_ns"] == w1 - w0
    (ops,) = REC["raw"]["devices"].values()
    assert red["busy_ns"] == sweep_busy(ops, w0, w1)
    assert 0 < red["busy_ns"] <= red["window_ns"]
    idle = sum(s for _, s in red["idle_gaps"]) * 1e9
    assert idle == pytest.approx(red["window_ns"] - red["busy_ns"], abs=10)


def test_kernel_time_is_the_sum_of_its_clipped_events():
    red = trace_reduce.reduce(REC["raw"])
    w0, w1 = window()
    (ops,) = REC["raw"]["devices"].values()
    for prefix in ("ring_scatter", "ingest_update", "gather_enrich"):
        want = sum(max(0, min(s + d, w1) - max(s, w0))
                   for n, s, d in ops if n.startswith(prefix))
        assert want > 0, prefix
        assert trace_reduce.kernel_ns(red, prefix) == want


def test_op_names_are_hlo_instruction_names():
    assert trace_reduce.op_name(
        "%ring_scatter.1 = u32[131072,10,16]{2,1,0:T(8,128)} "
        "custom-call(s32[4096,3]{1,0} %copy-done.37)") == "ring_scatter.1"
    assert trace_reduce.op_name("%fusion.5 = pred[8]{0} fusion(x)") == \
        "fusion.5"
    assert trace_reduce.op_name("copy.3") == "copy.3"


def test_no_window_or_no_device_reads_nothing():
    raw = {"devices": REC["raw"]["devices"],
           "host": [h for h in REC["raw"]["host"] if h[0] != "window"]}
    assert trace_reduce.reduce(raw) is None
    raw = {"devices": {}, "host": REC["raw"]["host"]}
    assert trace_reduce.reduce(raw) is None
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    for m in bench["per_layer"]:
        reader = run.load_module(os.path.join(HERE, "layers",
                                              m["name"] + ".py"), m["name"])
        if m["source"] == "device_trace":
            assert reader.read(ctx(None)) is None, m["name"]


def test_layer_readers():
    red = trace_reduce.reduce(REC["raw"])
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    got = {}
    for m in bench["per_layer"]:
        reader = run.load_module(os.path.join(HERE, "layers",
                                              m["name"] + ".py"), m["name"])
        got[m["name"]] = reader.read(ctx(red))
    n = REC["periods"]
    assert got["idle_share"] == pytest.approx(
        100 * (1 - red["busy_ns"] / red["window_ns"]))
    assert got["step_busy_ms"] == pytest.approx(red["busy_ns"] / n / 1e6)
    assert got["host_batch_ms"] == pytest.approx(
        1e3 * np.mean(REC["host_s"]))
    assert got["stale_fv_share"] == pytest.approx(100 * 2048 / (3 * 4096))
    assert got["ring_scatter_ms"] == pytest.approx(
        trace_reduce.kernel_ns(red, "ring_scatter") / n / 1e6)
    for k in ("ingest_update", "ring_scatter", "gather_enrich"):
        share = got[f"{k}_roofline"]
        least = sum(work.BYTES[k](w, REC["dfa"]) for w in REC["work"]) \
            / PEAKS["hbm_bytes_per_s"]
        assert share == pytest.approx(
            100 * least / (trace_reduce.kernel_ns(red, k) / 1e9))
        assert 0 < share <= 100, k


def test_roofline_byte_counts():
    dfa = {"history": 10, "derived_dim": 96}
    w = {"events": 1000, "slots_touched": 300, "reports": 4096,
         "placed": 4000}
    assert work.ingest_update_bytes(w, dfa) == 1000 * 33 + 300 * 85
    assert work.ring_scatter_bytes(w, dfa) == 4000 * 129 + 4096 * 9
    assert work.gather_enrich_bytes(w, dfa) == 4096 * (640 + 10 + 4 + 384)
    assert work.least_seconds("ring_scatter", [w, w], dfa, PEAKS) == \
        pytest.approx(2 * (4000 * 129 + 4096 * 9) / 819e9)
