"""The continuous serving loop + the structured streaming API.

Covers the ISSUE-6 contracts:

* exact drop accounting — ``offered == processed + dropped`` PER PERIOD
  when there is no carry-over queue, and cumulatively after a graceful
  drain when there is one, under a forced-overrun offered rate;
* latency percentile math against a hand-computed sample set;
* graceful shutdown drains in-flight periods (nothing is lost between
  "stop accepting" and "stop serving");
* a tier-1 smoke run of the real loop (host ring + donated step) for a
  handful of periods on the forced-host-device config;
* ``describe()`` key stability (the serving knobs are part of the
  contract now);
* the ``StepOutputs`` API — named access, ``stream()`` entry point,
  deprecated tuple shims warning exactly once per driver name;
* the ``configs.env`` registry — uniform fail-loud validation for every
  ``REPRO_*`` override.
"""
import dataclasses

import numpy as np
import pytest

from repro.compat import make_mesh
from repro.configs import env as ENV
from repro.configs import get_dfa_config
from repro.core.pipeline import DFASystem, StepOutputs
from repro.data import packets as PK
from repro.data.replay import TraceReplaySource
from repro.launch.serving import (ServingLoop, build_source,
                                  latency_summary, serve_trace)


def _trace(n_shards=1, T=3, E=128):
    return PK.period_batches(n_shards, T, E, n_flows=16, flow_seed=1)


def _source(E=64, T=3, **kw):
    events, nows = _trace(T=T, E=E)
    kw.setdefault("batch_events", E)
    kw.setdefault("budget_us", 20_000)
    return TraceReplaySource(events, nows, **kw)


def _capacity_eps(E=64, budget_us=20_000):
    return E / (budget_us / 1e6)


# -- replay source: pacing + exact accounting ---------------------------------

def test_line_rate_offers_full_batches_no_drops():
    src = _source()
    for _ in range(5):
        batch, now, acct = src.next_batch()
        assert acct == (64, 64, 0, 0)
        assert batch["valid"].all()
        assert (np.diff(batch["ts"].astype(np.int64)) >= 0).all()
    assert src.total.offered == src.total.processed == 5 * 64


def test_per_period_accounting_exact_without_queue():
    """queue_events=0 forced overrun: every single period closes its own
    books — offered == processed + dropped, nothing carried."""
    src = _source(offered_eps=2 * _capacity_eps(), queue_events=0)
    for _ in range(6):
        _, _, acct = src.next_batch()
        assert acct.offered == acct.processed + acct.dropped
        assert acct.queued == 0
        assert acct.offered == 128 and acct.processed == 64


def test_cumulative_accounting_with_queue_and_drain():
    src = _source(offered_eps=2 * _capacity_eps(), queue_events=96)
    for _ in range(6):
        src.next_batch()
    t = src.total
    assert t.dropped > 0, "2x capacity must overflow a 96-event queue"
    assert t.offered == t.processed + t.dropped + t.queued
    assert t.queued > 0
    src.begin_drain()
    while src.pending:
        _, _, acct = src.next_batch()
        assert acct.offered == 0          # shutdown accepts nothing new
    t = src.total
    assert t.offered == t.processed + t.dropped
    assert t.offered == 6 * 128


def test_drop_policy_newest_vs_oldest():
    """Tail-drop keeps the head of the arrival stream; head-drop keeps
    the tail — distinguishable by which five-tuples survive."""
    outs = {}
    for policy in ("newest", "oldest"):
        src = _source(offered_eps=2 * _capacity_eps(), queue_events=0,
                      drop_policy=policy)
        batch, _, acct = src.next_batch()
        assert acct.offered == 128 and acct.processed == 64
        assert acct.dropped == 64
        outs[policy] = batch["five_tuple"].copy()
    # tail-drop keeps arrivals 0..63, head-drop keeps 64..127
    assert not (outs["newest"] == outs["oldest"]).all()


def test_replay_validation_fails_loud():
    events, nows = _trace()
    with pytest.raises(ValueError, match="drop_policy"):
        TraceReplaySource(events, nows, batch_events=64,
                          drop_policy="coldest")
    with pytest.raises(ValueError, match="batch_events"):
        TraceReplaySource(events, nows, batch_events=0)
    with pytest.raises(ValueError, match="stacked"):
        TraceReplaySource({k: v[0] for k, v in events.items()}, nows,
                          batch_events=64)


def test_offered_rate_long_run_exact():
    """Fractional arrivals carry: a rate that isn't an integer multiple
    of the period still offers exactly rate*time events in the long run."""
    eps = 3_225.0                        # 64.5 events / 20 ms period
    src = _source(offered_eps=eps, queue_events=1 << 20)
    for _ in range(124):                 # 124 * 64.5 = 7998 exactly
        src.next_batch()
    assert src.total.offered == 7998


# -- latency percentile math --------------------------------------------------

def test_latency_summary_known_samples():
    # 1..100: linear-interp percentiles have closed forms
    s = latency_summary(list(range(1, 101)))
    assert s["p50"] == pytest.approx(50.5)
    assert s["p99"] == pytest.approx(99.01)
    assert s["p999"] == pytest.approx(99.901)
    # 4 samples, hand-computed: p50 midway, p99 interpolates the tail
    s4 = latency_summary([10.0, 20.0, 30.0, 40.0])
    assert s4["p50"] == pytest.approx(25.0)
    assert s4["p99"] == pytest.approx(39.7)
    assert s["count"] == 100 and s4["count"] == 4


def test_latency_summary_empty_is_explicit():
    """Zero samples -> an explicit empty summary: count pins it as "no
    data" and the percentiles are NaN, never a fake 0.0 latency."""
    empty = latency_summary([])
    assert empty["count"] == 0
    assert all(np.isnan(empty[k]) for k in ("p50", "p99", "p999"))
    assert set(empty) == {"p50", "p99", "p999", "count"}


def test_latency_summary_single_sample():
    """One period: every percentile of a single sample IS that sample —
    count=1 is what tells the consumer not to read a tail from it."""
    one = latency_summary([42.0])
    assert one["count"] == 1
    assert one["p50"] == one["p99"] == one["p999"] == 42.0


def test_zero_period_run_reports_explicit_empty():
    """A 0-period run must produce the explicit empty summary — not a
    ZeroDivisionError or NaN accounting."""
    mesh = make_mesh((1, 1), ("data", "model"))
    system = DFASystem(get_dfa_config(reduced=True), mesh)
    events, nows = _trace(system.n_shards, E=system.cfg.event_block)
    report = serve_trace(system, events, nows, periods=0, drain=False)
    assert report.periods == 0 and report.drained_periods == 0
    assert report.offered == report.processed == report.dropped == 0
    assert report.balanced
    assert report.latency["count"] == 0
    assert all(np.isnan(report.latency[k])
               for k in ("p50", "p99", "p999"))


def test_one_period_run_collapses_percentiles():
    mesh = make_mesh((1, 1), ("data", "model"))
    system = DFASystem(get_dfa_config(reduced=True), mesh)
    events, nows = _trace(system.n_shards, E=system.cfg.event_block)
    report = serve_trace(system, events, nows, periods=1)
    assert report.periods == 1
    lat = report.latency
    assert lat["count"] == 1
    assert lat["p50"] == lat["p99"] == lat["p999"] > 0.0


# -- the serving loop ---------------------------------------------------------

def test_serving_loop_smoke_line_rate():
    """Tier-1 smoke: the real loop (ring + donated step) for a handful
    of periods at line rate — full batches, zero drops, percentiles."""
    mesh = make_mesh((1, 1), ("data", "model"))
    system = DFASystem(get_dfa_config(reduced=True), mesh)
    events, nows = _trace(system.n_shards, E=system.cfg.event_block)
    report = serve_trace(system, events, nows, periods=5)
    assert report.periods == 5 and report.drained_periods == 0
    assert report.offered == report.processed == 5 * (
        system.n_shards * system.cfg.event_block)
    assert report.dropped == 0 and report.balanced
    assert len(report.latency_us) == 5
    assert set(report.latency) == {"p50", "p99", "p999", "count"}
    assert report.latency["count"] == 5
    assert isinstance(report.last, StepOutputs)
    assert report.last.enriched.shape[1] == system.cfg.derived_dim
    assert int(np.asarray(report.last.metrics["reports_recv"])) > 0


def test_serving_loop_snapshots_without_stalling(tmp_path):
    """Elastic satellite: with snapshot_every_periods set, the loop
    checkpoints the DFAState every N completed periods plus the final
    one — async, between block_until_ready and the next donated dispatch
    — and the newest snapshot equals the loop's end state bitwise."""
    import jax
    from repro.checkpoint import checkpoint as CKPT
    mesh = make_mesh((1, 1), ("data", "model"))
    cfg = dataclasses.replace(get_dfa_config(reduced=True),
                              snapshot_every_periods=2)
    system = DFASystem(cfg, mesh)
    events, nows = _trace(system.n_shards, E=system.cfg.event_block)
    source = build_source(system, events, nows)
    report = ServingLoop(system, source,
                         snapshot_dir=str(tmp_path)).run(5)
    assert report.periods == 5 and report.balanced
    # periods 2, 4 and the final 5 snapshot (keep=3 retains all three)
    assert report.snapshots == 3
    assert CKPT.list_steps(str(tmp_path)) == [2, 4, 5]
    restored, step = CKPT.restore(str(tmp_path))
    assert step == 5
    for a, b in zip(jax.tree.leaves(restored),
                    jax.tree.leaves(report.last.state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # and the knob off means zero snapshot side effects (default path)
    off = serve_trace(system, events, nows, periods=2)
    assert off.snapshots == 0


def test_serving_loop_forced_overrun_drains_on_shutdown():
    """Offered 2x the budget's capacity: the queue fills, the policy
    sheds exactly, and graceful shutdown serves the in-flight backlog
    (drained periods) so the books close."""
    mesh = make_mesh((1, 1), ("data", "model"))
    cfg = get_dfa_config(reduced=True)
    E = cfg.event_block
    cap = E / (cfg.monitoring_period_us / 1e6)
    cfg = dataclasses.replace(cfg, serve_offered_eps=2 * cap,
                              serve_queue_events=2 * E)
    system = DFASystem(cfg, mesh)
    events, nows = _trace(system.n_shards, E=E)
    report = serve_trace(system, events, nows, periods=6)
    assert report.dropped > 0
    assert report.drained_periods > 0, "shutdown must drain the queue"
    assert report.balanced, (report.offered, report.processed,
                             report.dropped)
    assert len(report.latency_us) == 6 + report.drained_periods
    # the drained backlog really went through the pipeline: the loop's
    # source is empty and every period's accounting row is consistent
    assert report.per_period[-1].queued == 0
    for acct in report.per_period:
        assert acct.offered >= 0 and acct.processed <= E


def test_serving_loop_no_drain_leaves_queue_accounted():
    mesh = make_mesh((1, 1), ("data", "model"))
    cfg = get_dfa_config(reduced=True)
    E = cfg.event_block
    cap = E / (cfg.monitoring_period_us / 1e6)
    cfg = dataclasses.replace(cfg, serve_offered_eps=2 * cap,
                              serve_queue_events=2 * E)
    system = DFASystem(cfg, mesh)
    events, nows = _trace(system.n_shards, E=E)
    source = build_source(system, events, nows)
    report = ServingLoop(system, source).run(4, drain=False)
    assert report.drained_periods == 0
    assert source.pending > 0
    assert report.offered == (report.processed + report.dropped
                              + source.pending)


@pytest.mark.multidevice
def test_serving_loop_rejects_indivisible_batch():
    mesh = make_mesh((2, 2), ("data", "model"))
    system = DFASystem(get_dfa_config(reduced=True), mesh)
    events, nows = _trace(T=2, E=63)
    src = TraceReplaySource(events, nows, batch_events=63)
    with pytest.raises(ValueError, match="divide across"):
        ServingLoop(system, src)


# -- describe(): serving knobs + key stability --------------------------------

DESCRIBE_KEYS = sorted([
    "kernel_backend", "gather_variant", "ingest_variant", "event_tile",
    "ingest_vmem_bytes", "ring_region_bytes", "vmem_budget_bytes",
    "gather_vmem_bytes", "n_shards", "flow_home", "pods",
    "shards_per_pod", "total_ports", "ports_per_device",
    "reporter_slots", "port_report_capacity", "overlap_periods",
    "inference_head", "serve_offered_eps", "serve_budget_us",
    "serve_queue_events", "drop_policy", "home_nodes",
    "snapshot_every_periods", "wire_format",
    "fault_injection", "rehome_collision_policy",
    "crosspod_exchange", "crosspod_capacity", "stage2_capacity",
    "tuning_registry",
])


def test_describe_reports_serving_knobs_and_keys_stable():
    mesh = make_mesh((1, 1), ("data", "model"))
    cfg = dataclasses.replace(get_dfa_config(reduced=True),
                              serve_offered_eps=1e6,
                              serve_queue_events=512,
                              drop_policy="oldest")
    d = DFASystem(cfg, mesh).describe()
    assert sorted(d) == DESCRIBE_KEYS, \
        "describe() keys are a stable contract — update DESCRIBE_KEYS " \
        "deliberately when adding fields"
    assert d["serve_offered_eps"] == 1e6
    assert d["serve_queue_events"] == 512
    assert d["drop_policy"] == "oldest"
    # budget resolves to the paper's monitoring period when unset
    assert d["serve_budget_us"] == cfg.monitoring_period_us
    d2 = DFASystem(dataclasses.replace(cfg, serve_budget_us=5_000),
                   mesh).describe()
    assert d2["serve_budget_us"] == 5_000


# -- StepOutputs + stream() ---------------------------------------------------

def test_stream_entry_point_matches_run_periods():
    mesh = make_mesh((1, 1), ("data", "model"))
    system = DFASystem(get_dfa_config(reduced=True), mesh)
    events, nows = _trace(system.n_shards, T=2, E=system.cfg.event_block)
    with system.mesh:
        a = system.stream(system.init_state(), events, nows)
        b = system.stream(system.init_state(), events, nows,
                          overlapped=True)
    assert isinstance(a, StepOutputs) and isinstance(b, StepOutputs)
    assert a.preds is None and b.preds is None
    np.testing.assert_array_equal(np.asarray(a.mask), np.asarray(b.mask))
    np.testing.assert_allclose(np.asarray(a.enriched),
                               np.asarray(b.enriched),
                               rtol=1e-6, atol=1e-6)


def test_step_outputs_arity_is_fixed():
    """The whole point of the redesign: preds presence never changes the
    field count."""
    assert StepOutputs._fields == ("state", "enriched", "flow_ids",
                                   "mask", "metrics", "preds")
    out5 = StepOutputs("s", "e", "f", "m", {})
    assert out5.preds is None
    out6 = StepOutputs("s", "e", "f", "m", {}, preds="p")
    assert out6.preds == "p" and len(out6) == 6


def test_deprecated_tuple_shims_are_gone():
    """The PR 6 deprecation window closed: the `*_tuple` drivers and the
    variadic `as_tuple()` view no longer exist — callers consume
    StepOutputs fields by name."""
    for name in ("dfa_step_tuple", "run_periods_tuple",
                 "run_periods_overlapped_tuple", "_tuple_shim"):
        assert not hasattr(DFASystem, name), \
            f"removed shim {name} reappeared"
    assert not hasattr(StepOutputs, "as_tuple")


# -- configs.env: the one override registry -----------------------------------

def test_env_registry_covers_all_repro_vars():
    names = set(ENV.registered())
    assert names == {"REPRO_KERNEL_BACKEND", "REPRO_GATHER_VARIANT",
                     "REPRO_INGEST_VARIANT", "REPRO_BENCH_TINY",
                     "REPRO_REGEN_GOLDENS", "REPRO_WIRE_FORMAT",
                     "REPRO_TUNING_REGISTRY"}
    table = ENV.env_table()
    for n in names:
        assert n in table


def test_env_choice_fail_loud(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "palas")
    with pytest.raises(ValueError) as e:
        ENV.read_choice("REPRO_KERNEL_BACKEND")
    msg = str(e.value)
    assert "REPRO_KERNEL_BACKEND" in msg and "pallas" in msg
    for ok, expect in (("", None), ("auto", None), ("REF", "ref"),
                       (" pallas ", "pallas")):
        monkeypatch.setenv("REPRO_KERNEL_BACKEND", ok)
        assert ENV.read_choice("REPRO_KERNEL_BACKEND") == expect


def test_env_flag_fail_loud(monkeypatch):
    for raw, want in (("", False), ("0", False), ("false", False),
                      ("no", False), ("off", False), ("1", True),
                      ("true", True), ("YES", True), ("on", True)):
        monkeypatch.setenv("REPRO_BENCH_TINY", raw)
        assert ENV.read_flag("REPRO_BENCH_TINY") is want
    monkeypatch.setenv("REPRO_BENCH_TINY", "maybe")
    with pytest.raises(ValueError, match="REPRO_BENCH_TINY|maybe"):
        ENV.read_flag("REPRO_BENCH_TINY")


def test_env_unregistered_name_rejected():
    with pytest.raises(KeyError, match="unregistered"):
        ENV.read_flag("REPRO_NOT_A_THING")
    with pytest.raises(KeyError, match="unregistered"):
        ENV.spec("REPRO_NOT_A_THING")
