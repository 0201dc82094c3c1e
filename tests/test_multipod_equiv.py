"""Pod-count invariance of the 2D (pod, shard) mesh stream.

This container is CPU-only, so the correctness of the multi-pod routing
layer (per-port reporter tables, hash-home flow ids, two-stage intra-pod/
cross-pod exchange, home-side canonical re-ordering) is carried entirely
by this differential harness: for every scenario in
``repro.data.scenarios`` the SAME port-major traffic trace is streamed
through a ``(1, S)``, ``(2, S)`` and ``(4, S//2)`` mesh holding the
global ring keyspace fixed (``flows_per_shard = G / n_devices``), and the
merged end state plus every per-period metric delta must be BITWISE
identical — for both drivers (``run_periods`` /
``run_periods_overlapped``) and with the inference head on and off.

Canonical re-gather: reporter state is already port-major-global (one
table per port, identical layout on every mesh); translator counters and
the collector ring concatenate pod-major into the (G, ...) keyspace;
``last_seq`` merges by elementwise max (a monotone tracker — a port's
reports spread over devices differently per mesh); the scalar telemetry
counters merge by sum. Per-period enriched features / flow ids / preds
are compared as flow-id-sorted sets (row order inside a period is a
mesh-dependent exchange artifact; the VALUES must match bitwise).

Compile cost dominates: systems and jitted drivers are cached per
(mesh, head) and shared across all scenarios (same shapes), so the whole
grid pays 12 small SPMD compiles. The 8-device (1,4)/(2,4)/(4,2) family
re-runs two scenarios and is marked slow for the nightly.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import pod_mesh_or_skip
from repro.configs.dfa import (REDUCED, REDUCED_MULTIPOD,
                               REDUCED_MULTIPOD_V2)
from repro.core import translator as TRANS
from repro.core.pipeline import DFASystem
from repro.data import scenarios as SC

TOTAL_PORTS = 4
EVENTS_PER_PORT = 48
T = 3
G = 512                  # global ring keyspace, fixed across meshes
REPORTER_SLOTS = 64      # per-PORT Marina table, fixed across meshes
PORT_CAPACITY = 16       # per-port due-report capacity

GRID = ((1, 2), (2, 2), (4, 1))          # S=2 family (<= 4 devices)
GRID_WIDE = ((1, 4), (2, 4), (4, 2))     # S=4 family (8 devices, slow)

SCENARIOS = sorted(SC.SCENARIOS)

_systems = {}
_traces = {}


def _mesh_cfg(pods, shards, head, total_ports):
    ndev = pods * shards
    return dataclasses.replace(
        REDUCED,
        flow_home="hash",
        pods=pods,
        ports_per_pod=total_ports // pods,
        reporter_slots=REPORTER_SLOTS,
        flows_per_shard=G // ndev,
        port_report_capacity=PORT_CAPACITY,
        kernel_backend="ref",
        inference_head=head)


def _system(pods, shards, head, total_ports=TOTAL_PORTS):
    key = (pods, shards, head, total_ports)
    if key not in _systems:
        mesh = pod_mesh_or_skip(pods, shards)
        sysm = DFASystem(_mesh_cfg(pods, shards, head, total_ports),
                         mesh)
        _systems[key] = (sysm, jax.jit(sysm.run_periods),
                         jax.jit(sysm.run_periods_overlapped))
    return _systems[key]


def _trace(name, total_ports=TOTAL_PORTS):
    key = (name, total_ports)
    if key not in _traces:
        ev, nows = SC.build(name, total_ports, EVENTS_PER_PORT, T)
        _traces[key] = ({k: jnp.asarray(v) for k, v in ev.items()},
                        jnp.asarray(nows))
    return _traces[key]


def _merged_state(system, state):
    """Canonical re-gather: mesh-shape-independent view of DFAState."""
    n = system.n_shards
    out = {f"rep.{k}": np.asarray(a)
           for k, a in state.reporter._asdict().items()}
    out["tr.hist_counter"] = np.asarray(state.translator.hist_counter)
    c = state.collector
    out["coll.memory"] = np.asarray(c.memory)
    out["coll.entry_valid"] = np.asarray(c.entry_valid)
    out["coll.last_seq"] = np.asarray(c.last_seq).reshape(n, -1).max(0)
    for k in ("bad_checksum", "seq_anomalies", "received",
              "lost_reports"):
        out[f"coll.{k}"] = np.asarray(getattr(c, k)).astype(
            np.uint64).sum()
    return out


def _canon_periods(enr, fid, em, preds=None):
    """Per period: (sorted flow ids, enriched rows in that order[, preds])
    — the mesh-invariant content of the period's output batch."""
    enr, fid, em = np.asarray(enr), np.asarray(fid), np.asarray(em)
    preds = None if preds is None else np.asarray(preds)
    per = []
    for t in range(enr.shape[0]):
        m = em[t]
        order = np.argsort(fid[t][m], kind="stable")
        row = {"fid": fid[t][m][order], "enr": enr[t][m][order]}
        if preds is not None:
            row["preds"] = preds[t][m][order]
        per.append(row)
    return per


def _run(pods, shards, head, overlapped, scenario,
         total_ports=TOTAL_PORTS):
    sysm, seq, ovl = _system(pods, shards, head, total_ports)
    events, nows = _trace(scenario, total_ports)
    with sysm.mesh:
        out = (ovl if overlapped else seq)(sysm.init_state(), events,
                                           nows)
    assert (out.preds is None) == (head == "none")
    return (_merged_state(sysm, out.state),
            _canon_periods(out.enriched, out.flow_ids, out.mask,
                           out.preds),
            {k: np.asarray(v) for k, v in out.metrics.items()})


def _assert_same(ref, got, ctx):
    rst, rout, rmet = ref
    gst, gout, gmet = got
    for k in rst:
        np.testing.assert_array_equal(rst[k], gst[k],
                                      err_msg=f"{ctx}: state {k}")
    assert sorted(rmet) == sorted(gmet)
    for k in rmet:
        np.testing.assert_array_equal(rmet[k], gmet[k],
                                      err_msg=f"{ctx}: metric {k}")
    for t, (r, g) in enumerate(zip(rout, gout)):
        for k in r:
            np.testing.assert_array_equal(
                r[k], g[k], err_msg=f"{ctx}: period {t} {k}")


def _check_grid(grid, scenario, head, total_ports=TOTAL_PORTS):
    for overlapped in (False, True):
        ref = _run(*grid[0], head, overlapped, scenario, total_ports)
        assert int(ref[2]["reports_recv"].sum()) > 0, \
            f"{scenario}: trace produced no routed reports"
        assert int(ref[2]["bucket_drops"].sum()) == 0
        # validity bound of the invariance contract: once a port's
        # lifetime report count passes the wire format's seq space, the
        # collector's per-DEVICE §VI-B dup window can fire differently
        # per mesh factorization (each device sees a mesh-dependent
        # subset of a reporter's seq stream). Scenarios must stay under
        # the wrap — assert it so a future longer trace fails here, not
        # as an inscrutable seq_anomalies mismatch. The bound comes off
        # the schema, not a hard-coded 256: V2 traces get the u16 space.
        wf = _system(*grid[0], head, total_ports)[0].wire
        assert (ref[0]["rep.seq"] <= wf.seq_mask).all(), \
            f"{scenario}: a port wrapped its {wf.seq_width}-bit seq; " \
            "invariance of seq_anomalies is not guaranteed past the wrap"
        for pods, shards in grid[1:]:
            got = _run(pods, shards, head, overlapped, scenario,
                       total_ports)
            _assert_same(ref, got,
                         f"{scenario} head={head} "
                         f"ovl={overlapped} ({pods},{shards}) vs "
                         f"{grid[0]}")


@pytest.mark.parametrize("scenario", SCENARIOS)
def test_pod_count_invariance(scenario):
    """(1,2) == (2,2) == (4,1), both drivers, no inference head."""
    _check_grid(GRID, scenario, "none")


@pytest.mark.parametrize("scenario", ["elephants_mice", "cross_pod_mix",
                                      "flow_churn", "collision_storm",
                                      "u32_wrap"])
def test_pod_count_invariance_with_inference(scenario):
    """Same grid with the linear verdict head armed: preds ride the
    enrich half, so they must be pod-count invariant too."""
    _check_grid(GRID, scenario, "linear")


@pytest.mark.multidevice
@pytest.mark.slow
@pytest.mark.parametrize("scenario", ["elephants_mice", "cross_pod_mix"])
def test_pod_count_invariance_wide(scenario):
    """The 8-device S=4 family (1,4)/(2,4)/(4,2) — nightly-sized.

    8 ports (one per device on the widest meshes, 2/device on (1,4))
    instead of tier-1's 4: total_ports must be a device-count multiple
    on every mesh in the family."""
    _check_grid(GRID_WIDE, scenario, "none", total_ports=8)


def test_pod22_stream_smoke():
    """In-process (2,2)-pod streaming check (the tier-1 CI anchor):
    REDUCED_MULTIPOD on a real (2,2) mesh streams both drivers
    output-identically, reports actually cross pods, and describe()
    surfaces the topology."""
    mesh = pod_mesh_or_skip(2, 2)
    sysm = DFASystem(dataclasses.replace(REDUCED_MULTIPOD,
                                         kernel_backend="ref"), mesh)
    ev, nows = SC.build("cross_pod_mix", sysm.total_ports, 32, T)
    events = {k: jnp.asarray(v) for k, v in ev.items()}
    nows = jnp.asarray(nows)
    with sysm.mesh:
        seq = jax.jit(sysm.run_periods)(sysm.init_state(), events, nows)
        ovl = jax.jit(sysm.run_periods_overlapped)(sysm.init_state(),
                                                   events, nows)
    fid, em, met = seq.flow_ids, seq.mask, seq.metrics
    assert int(np.asarray(met["reports_recv"]).sum()) > 0
    # cross-pod delivery really happened: some flow ingested by a pod-0
    # port is homed on pod 1 (or vice versa) — with hash homes over a
    # shared flow set this is overwhelmingly likely, and deterministic
    # for the fixed seed
    fps = sysm.cfg.flows_per_shard
    homes = np.asarray(fid)[np.asarray(em)].astype(np.int64) // fps
    home_pods = homes // sysm.shards_per_pod
    assert set(home_pods.tolist()) == {0, 1}, \
        "trace never exercised the cross-pod exchange"
    # overlapped driver is output-identical on the pod mesh too
    for a, b in zip(jax.tree.leaves(seq), jax.tree.leaves(ovl)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    d = sysm.describe()
    assert d["flow_home"] == "hash" and d["pods"] == 2
    assert d["total_ports"] == 4 and d["ports_per_device"] == 1


def test_single_device_multiport_mesh():
    """Degenerate (1,1) pod mesh hosting all ports: the two-stage fabric
    collapses to identity exchanges but the per-port tables, hash homes
    and canonical ordering still run — this is the shape the bench-smoke
    pod rows use on 1-device CI runners, so pin it here."""
    mesh = pod_mesh_or_skip(1, 1)
    cfg = dataclasses.replace(
        REDUCED, flow_home="hash", ports_per_pod=4, reporter_slots=64,
        flows_per_shard=256, port_report_capacity=16,
        kernel_backend="ref")
    sysm = DFASystem(cfg, mesh)
    assert sysm.ports_per_device == 4
    ev, nows = SC.build("elephants_mice", 4, 32, T)
    with sysm.mesh:
        out = jax.jit(sysm.run_periods)(
            sysm.init_state(), {k: jnp.asarray(v) for k, v in ev.items()},
            jnp.asarray(nows))
    fid, em, met = out.flow_ids, out.mask, out.metrics
    assert int(np.asarray(met["reports_recv"]).sum()) > 0
    assert int(np.asarray(met["bucket_drops"]).sum()) == 0
    # every routed flow id is a hash home inside the global keyspace
    fids = np.asarray(fid)[np.asarray(em)]
    assert (fids < sysm.total_flows).all()


def test_port_count_beyond_reporter_id_space_refused():
    """Under V1, >256 ports would alias two ports onto one 8-bit reporter
    id and silently break canonical ordering — the constructor must
    refuse (and point at the wide format)."""
    mesh = pod_mesh_or_skip(1, 1)
    cfg = dataclasses.replace(
        REDUCED, flow_home="hash", ports_per_pod=512,
        reporter_slots=64, port_report_capacity=1)
    with pytest.raises(ValueError, match="8-bit reporter id") as e:
        DFASystem(cfg, mesh)
    assert "v2" in str(e.value), \
        "the refusal should tell the operator about wire_format='v2'"


# -- the V2 wide format: the 256-port cap is a schema property ------------
#
# Same differential contract as the V1 grid, run past the V1 wall: 264
# virtual ports (> the 8-bit reporter-id space) stream the vectorized
# wide_port_sweep trace through three mesh factorizations under
# wire_format="v2", and the merged state / per-period outputs / metrics
# must stay bitwise identical. Trace is short (T=2, 2 events/port) —
# the point is reporter ids above 255 surviving the whole
# pack->route->unpack->canonical-sort path, not traffic volume.

V2_PORTS = 264
V2_EVENTS_PER_PORT = 2
V2_T = 2
V2_G = 8192              # global ring keyspace, fixed across meshes
V2_GRID = ((1, 2), (2, 2), (4, 1))


def _mesh_cfg_v2(pods, shards):
    ndev = pods * shards
    return dataclasses.replace(
        REDUCED_MULTIPOD_V2,
        pods=pods,
        ports_per_pod=V2_PORTS // pods,
        flows_per_shard=V2_G // ndev,
        port_report_capacity=4,
        kernel_backend="ref")


def _run_v2(pods, shards, overlapped, scenario):
    key = ("v2", pods, shards)
    if key not in _systems:
        mesh = pod_mesh_or_skip(pods, shards)
        sysm = DFASystem(_mesh_cfg_v2(pods, shards), mesh)
        _systems[key] = (sysm, jax.jit(sysm.run_periods),
                         jax.jit(sysm.run_periods_overlapped))
    sysm, seq, ovl = _systems[key]
    tkey = ("v2", scenario)
    if tkey not in _traces:
        ev, nows = SC.build(scenario, V2_PORTS, V2_EVENTS_PER_PORT, V2_T)
        _traces[tkey] = ({k: jnp.asarray(v) for k, v in ev.items()},
                         jnp.asarray(nows))
    events, nows = _traces[tkey]
    with sysm.mesh:
        out = (ovl if overlapped else seq)(sysm.init_state(), events,
                                           nows)
    return (sysm,
            (_merged_state(sysm, out.state),
             _canon_periods(out.enriched, out.flow_ids, out.mask),
             {k: np.asarray(v) for k, v in out.metrics.items()}))


def test_v2_accepts_port_counts_past_v1_wall():
    """The config-level 256-port refusal is gone under V2: the same 512
    ports V1 rejects construct cleanly, and describe() says why."""
    mesh = pod_mesh_or_skip(1, 1)
    cfg = dataclasses.replace(
        REDUCED, flow_home="hash", wire_format="v2", ports_per_pod=512,
        reporter_slots=8, port_report_capacity=1)
    sysm = DFASystem(cfg, mesh)
    assert sysm.total_ports == 512 and sysm.wire.name == "v2"
    assert sysm.describe()["wire_format"] == "v2"


def test_v2_pod_count_invariance_past_256_ports():
    """THE V2 acceptance differential: 264 ports (> V1's 8-bit space) are
    pod-count invariant under wire_format='v2', both drivers."""
    for overlapped in (False, True):
        ref_sys, ref = _run_v2(*V2_GRID[0], overlapped,
                               "wide_port_sweep")
        assert ref_sys.wire.seq_mask == 0xFFFF
        assert int(ref[2]["reports_recv"].sum()) > 0
        assert int(ref[2]["bucket_drops"].sum()) == 0
        # ports past the V1 wall really reported: per-port seq counters
        # above index 255 advanced, so reporter ids >255 crossed the wire
        assert (np.asarray(ref[0]["rep.seq"])[256:] > 0).any(), \
            "no port beyond the 8-bit space ever reported — the trace " \
            "does not exercise the widened field"
        assert (ref[0]["rep.seq"] <= ref_sys.wire.seq_mask).all()
        for pods, shards in V2_GRID[1:]:
            _, got = _run_v2(pods, shards, overlapped, "wide_port_sweep")
            _assert_same(ref, got,
                         f"v2 wide_port_sweep ovl={overlapped} "
                         f"({pods},{shards}) vs {V2_GRID[0]}")


def test_config_mesh_pod_mismatch_refused():
    """cfg.pods must agree with the mesh's pod axis — a silent mismatch
    would resize the port set out from under the config."""
    mesh = pod_mesh_or_skip(2, 2)
    with pytest.raises(ValueError, match="pod axis"):
        DFASystem(dataclasses.replace(REDUCED_MULTIPOD, pods=4), mesh)


def test_indivisible_event_split_refused():
    """An event batch that doesn't divide across a device's hosted ports
    must fail at trace time, not silently drop trailing events."""
    mesh = pod_mesh_or_skip(1, 1)
    cfg = dataclasses.replace(
        REDUCED, flow_home="hash", ports_per_pod=4, reporter_slots=64,
        flows_per_shard=256, port_report_capacity=8,
        kernel_backend="ref")
    sysm = DFASystem(cfg, mesh)
    ev, nows = SC.build("port_local", 4, 32, 1)
    events = {k: jnp.asarray(v[0][:-2] if v[0].ndim == 1
                             else v[0][:-2, :]) for k, v in ev.items()}
    with pytest.raises(ValueError, match="divide across"):
        with sysm.mesh:
            jax.jit(sysm.dfa_step)(sysm.init_state(), events,
                                   jnp.asarray(nows)[0])


def test_home_assignment_matches_translator():
    """The flow ids the stream emits agree with translator.home_flow_ids
    of the flows' five-tuples (home = hash of key, not of ingest port)."""
    mesh = pod_mesh_or_skip(2, 2)
    sysm, seq, _ = _system(2, 2, "none")
    events, nows = _trace("port_local")
    with sysm.mesh:
        out = seq(sysm.init_state(), events, nows)
    state, fid, em = out.state, out.flow_ids, out.mask
    # reconstruct home ids for every ACTIVE reporter key, then check all
    # routed flow ids are in that set
    keys = np.asarray(state.reporter.keys)[np.asarray(
        state.reporter.active)]
    expect = set(np.asarray(TRANS.home_flow_ids(
        jnp.asarray(keys), sysm.total_flows)).tolist())
    got = set(np.asarray(fid)[np.asarray(em)].tolist())
    assert got <= expect
    assert got, "no flows routed"


@pytest.mark.parametrize("scenario", ["elephants_mice", "collision_storm"])
def test_pod_mesh_pallas_kernels_match_ref(scenario):
    """The (2, 2) mesh with the Pallas kernels (interpret) against the
    ref backend. The home side's canonical (flow, reporter, seq) order
    puts several reports of one flow, from different ports, in one
    batch, each placed in its own history entry. End state, every
    per-period metric and flow id bitwise; features within 1e-5 of their
    row's scale (the tolerance of test_gather_enrich_equiv)."""
    mesh = pod_mesh_or_skip(2, 2)
    sysm = DFASystem(dataclasses.replace(_mesh_cfg(2, 2, "none",
                                                   TOTAL_PORTS),
                                         kernel_backend="interpret"), mesh)
    events, nows = _trace(scenario)
    with sysm.mesh:
        out = jax.jit(sysm.run_periods)(sysm.init_state(), events, nows)
    rst, rout, rmet = _run(2, 2, "none", False, scenario)
    gst = _merged_state(sysm, out.state)
    for k in rst:
        np.testing.assert_array_equal(gst[k], rst[k], err_msg=k)
    for k in rmet:
        np.testing.assert_array_equal(np.asarray(out.metrics[k]), rmet[k],
                                      err_msg=k)
    gout = _canon_periods(out.enriched, out.flow_ids, out.mask)
    for t, (g, r) in enumerate(zip(gout, rout)):
        np.testing.assert_array_equal(g["fid"], r["fid"], f"period {t}")
        want = r["enr"]
        fin = np.isfinite(want)
        np.testing.assert_array_equal(np.isfinite(g["enr"]), fin)
        want = np.where(fin, want, 0.0)
        scale = np.maximum(1.0, np.abs(want).max(axis=-1, keepdims=True))
        err = np.abs(np.where(fin, g["enr"], 0.0) - want) / scale
        assert err.max(initial=0.0) <= 1e-5, (t, err.max())
