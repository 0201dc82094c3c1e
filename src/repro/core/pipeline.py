"""End-to-end distributed DFA pipeline (Fig 1) as one SPMD step.

Every device is simultaneously one Reporter shard and one Collector shard
(+ its translator): the flow space is range-sharded over the *entire* mesh
(512 shards × 2^17 flows = 67M flows at production scale — the paper's
4-pipeline Tofino supports 524,288). One ``dfa_step``:

  local packet events ──ingest──> per-flow Table-I registers
  due flows ──clone/truncate──> DTA reports (fixed capacity)
  reports ──all_to_all over ("pod","data","model")──> owner shards
           (the ICI takes RoCEv2's place; addresses computed by the
            owner-side translator exactly as §III-B)
  payloads ──ring placement──> (F, 10, 16-word) collector memory (Fig 4)
  received flows ──enrichment──> derived feature vectors -> inference

Every hot stage (moment accumulation, ring placement, gather+enrichment)
routes through the kernel dispatch registry (repro.kernels.dispatch):
``DFAConfig.kernel_backend`` / ``REPRO_KERNEL_BACKEND`` select ref / pallas
/ interpret per run, with the Pallas kernels jitting inside ``shard_map``
(shard-local shapes are static).

The step is jit-compatible, state is donated (in-place ring updates — the
GDR analogue), and every stage has a fixed SPMD shape.

One monitoring period is two explicit half-steps:

  ``ingest_half``  — reporter ingest, due-flow reports, all_to_all
                     routing, translator addressing, ring placement;
                     returns the period's :class:`RoutedBatch` coords
  ``enrich_half``  — history gather + enrichment of those routed flows
                     (plus the optional immediate-inference hook: a
                     model head from ``models.registry.get_flow_head``
                     consuming the (R, derived_dim) features in the
                     same trace)

``run_periods`` chains both halves per period under one ``lax.scan``;
``run_periods_overlapped`` software-pipelines the stream — the carry holds
period t's routed coords so its enrich half runs in the same scan body as
period t+1's ingest half (one warm-up ingest, one drain enrich). The two
drivers are output-identical by construction: the deferred enrich still
reads the ring AFTER period t's placement and BEFORE period t+1's, so
enrichment latency no longer eats the next period's ingest budget without
changing a single emitted feature.

Per-period ``metrics`` are all deltas: ``collisions`` / ``bad_checksum`` /
``seq_anomalies`` report what THIS period added (the cumulative counters
stay in the state), matching ``reports_sent`` / ``reports_recv`` /
``bucket_drops`` which were always per-period. ``reports_due`` counts the
flows due a report before the report capacity cuts them, so
``reports_due - reports_sent`` is what the period deferred.

Every stage runs under a ``jax.named_scope`` — ``reporter`` (``ingest``,
``due``, ``reports``), ``route``, ``exchange`` (the (pod, shard) mesh),
``translate``, ``faults`` (when armed), ``collector`` (``validate``,
``place``) and ``enrich`` (``infer`` when a head is armed) — so each
device op's ``op_name`` metadata, and with it a profiler trace, names
the stage that owns it.

Multi-pod (2D mesh) streaming: with ``cfg.flow_home == "hash"`` the same
drivers run on a ``(pod, shard)`` mesh (``launch.mesh.make_dfa_mesh``).
Each pod owns a disjoint set of reporter PORTS (independent per-port
Marina tables, ``cfg.ports_per_pod``), a flow's home ring is the range
shard of its hashed key in the GLOBAL keyspace (``translator
.home_flow_ids``), and delivery is two-stage: intra-pod ``all_to_all``
over the shard fabric, then a cross-pod exchange over the pod axis for
flows whose home pod differs from their ingest pod. The home translator
canonically re-orders arrivals, which makes the merged end state bitwise
independent of how the same port set factors into pods — the property
``tests/test_multipod_equiv.py`` pins scenario by scenario.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import DFAConfig
from repro.core import collector as COLL
from repro.core import protocol as PROTO
from repro.core import reporter as REP
from repro.core import translator as TRANS
from repro.core import wire as WIRE
from repro.data import faults as FAULTS
from repro.kernels import dispatch
from repro.kernels import tuning as TUNING

Tree = Any


class DFAState(NamedTuple):
    reporter: REP.ReporterState
    translator: TRANS.TranslatorState
    collector: COLL.CollectorState


def _global_seq_gap(coll_st, lseq0, recv0, lost0, dev, ax):
    """Supersede the collector's shard-local seq-gap count with the
    global one (inside the ingest shard_map, after COLL.ingest).

    A reporter's seq stream fans out across flow-home shards, so each
    shard's local §VI-B window multi-counts advances that were simply
    routed elsewhere. Globally the accounting is exact: per reporter,
    the window advance (max over shards — seqs are minted contiguously)
    minus the accepted arrivals summed over shards is precisely the
    number of reports that never landed anywhere (dropped in flight, or
    discarded as corrupted). The global count lands on the lead shard so
    summing scalars across shards — what every merge/differential
    harness does — stays exact; ``lost0`` is the shard's pre-ingest
    value, discarding the routing-polluted local delta.
    """
    advanced = (jnp.sum(jax.lax.pmax(coll_st.last_seq, ax))
                - jnp.sum(jax.lax.pmax(lseq0, ax)))
    arrivals = jax.lax.psum(jnp.sum(coll_st.received - recv0), ax)
    lost_delta = (advanced - arrivals).astype(jnp.uint32)
    lost = lost0 + jnp.where(dev == 0, lost_delta, jnp.uint32(0))
    # counters ride the state as per-shard (1,) slices of an (n_shards,)
    # array — keep that local shape
    return coll_st._replace(
        lost_reports=lost.reshape(coll_st.lost_reports.shape)), lost_delta


class RoutedBatch(NamedTuple):
    """One period's routing products, carried from the ingest half into
    the (possibly deferred) enrich half — everything enrichment needs, so
    nothing is re-derived. All arrays are mesh-sharded over their leading
    dim exactly like the event batch (P(axes))."""
    local_flow: jax.Array   # (R,) i32 — owner-shard-local flow coords
    flow_id: jax.Array      # (R,) u32 — global flow ids (report word 0)
    mask: jax.Array         # (R,) bool — routed-report validity


class StepOutputs(NamedTuple):
    """The structured return of every driver (``dfa_step``,
    ``run_periods``, ``run_periods_overlapped``, ``stream``).

    Field arity is FIXED: ``preds`` is always present and is ``None``
    unless an inference head is armed — unlike the historical variadic
    5-or-6-tuple, whose length depended on ``cfg.inference_head`` and
    forced every continuous caller to branch on arity. Streaming drivers
    stack each per-period field under a leading (T,) dim.

    Unpack by name (``out.state``, ``out.enriched`` ...). The deprecated
    positional accessors (``as_tuple`` and the ``*_tuple`` driver shims)
    were removed after their one-release grace window.
    """
    state: DFAState                     # post-period system state
    enriched: jax.Array                 # ([T,] R, derived_dim) f32
    flow_ids: jax.Array                 # ([T,] R) u32 (0xFFFFFFFF = pad)
    mask: jax.Array                     # ([T,] R) bool validity
    metrics: Dict[str, jax.Array]       # per-period delta counters
    preds: Optional[jax.Array] = None   # ([T,] R, C) when a head is armed


class DFASystem:
    """Facade: builds sharded state + the jit-able distributed step.

    ``infer_fn`` (optional): ``feats (R, derived_dim) -> preds`` applied
    inside the enrich half — immediate inference on the just-enriched
    features. When omitted and ``cfg.inference_head != "none"`` a head is
    built from ``models.registry.get_flow_head`` (params on
    ``self.infer_params``); with the default head "none" every driver
    keeps its historical 5-tuple returns."""

    def __init__(self, cfg: DFAConfig, mesh: Mesh, infer_fn=None):
        self.cfg = cfg
        self.mesh = mesh
        self.axes = tuple(mesh.axis_names)
        self.n_shards = int(math.prod(mesh.devices.shape))
        # active wire schema (env > cfg.wire_format > "v1"), resolved
        # once — fail-loud on junk, and topology caps derive from it
        self.wire = WIRE.resolve(cfg)
        self._derive_topology()
        self.infer_params: Optional[Tree] = None
        if infer_fn is None and cfg.inference_head != "none":
            from repro.models.registry import get_flow_head  # lazy: heavy
            self.infer_params, head = get_flow_head(cfg, jax.random.key(0))
            params = self.infer_params
            infer_fn = lambda feats: head(params, feats)  # noqa: E731
        self.infer_fn = infer_fn

    def _derive_topology(self) -> None:
        """(pod, shard) mesh factorization + port placement.

        The MESH is authoritative: ``pods`` is the size of the axis named
        "pod" when present (1 otherwise) and the remaining axes form the
        intra-pod shard fabric. ``cfg.flow_home`` picks the routing
        scheme; "hash" additionally activates per-port reporter tables
        (``cfg.ports_per_pod`` ports per pod, hosted
        ``total_ports / n_devices`` per device in pod-major order, so pods
        own disjoint contiguous port ranges)."""
        cfg = self.cfg
        sizes = dict(zip(self.axes, self.mesh.devices.shape))
        self.pod_axis = "pod" if "pod" in self.axes else None
        if self.pod_axis and self.axes[0] != "pod":
            raise ValueError(
                f"the 'pod' axis must be the leading mesh axis (pod-major "
                f"device order); got axes {self.axes}")
        self.shard_axes = tuple(a for a in self.axes if a != "pod")
        self.mesh_pods = int(sizes.get("pod", 1))
        self.shards_per_pod = self.n_shards // self.mesh_pods
        self.total_flows = self.n_shards * cfg.flows_per_shard
        if cfg.flow_home not in ("ingest", "hash", "rendezvous"):
            raise ValueError(
                f"flow_home must be 'ingest', 'hash' or 'rendezvous', got "
                f"{cfg.flow_home!r}")
        self.multipod = cfg.flow_home in ("hash", "rendezvous")
        if cfg.crosspod_exchange not in ("padded", "ragged"):
            raise ValueError(
                f"crosspod_exchange must be 'padded' or 'ragged', got "
                f"{cfg.crosspod_exchange!r}")
        self.crosspod_exchange = cfg.crosspod_exchange
        if cfg.crosspod_capacity < 0:
            raise ValueError(
                f"crosspod_capacity must be >= 0 (0 = worst-case "
                f"auto-size), got {cfg.crosspod_capacity}")
        if not self.multipod:
            if cfg.crosspod_exchange != "padded":
                raise ValueError(
                    "crosspod_exchange='ragged' compresses the stage-2 "
                    "pod exchange, which only exists under "
                    "flow_home='hash'/'rendezvous'; the legacy 'ingest' "
                    "scheme has no pod stage to compress")
            if cfg.crosspod_capacity:
                raise ValueError(
                    "crosspod_capacity sizes the ragged stage-2 segments "
                    "and is meaningless under flow_home='ingest'")
        if cfg.flow_home == "rendezvous":
            nodes = tuple(cfg.home_nodes) or tuple(range(self.n_shards))
            if len(nodes) != self.n_shards:
                raise ValueError(
                    f"home_nodes has {len(nodes)} entries for a "
                    f"{self.n_shards}-device mesh: one logical node id "
                    "per device (pod-major), so the rendezvous winner "
                    "set and the mesh agree on who owns what")
            if any(b <= a for a, b in zip(nodes, nodes[1:])) or nodes[0] < 0:
                raise ValueError(
                    f"home_nodes must be strictly increasing non-negative "
                    f"ids, got {nodes}: sorted order is what keeps HRW "
                    "tie-breaking and node_position lookups mesh-invariant")
            self.home_nodes: Tuple[int, ...] = nodes
        else:
            self.home_nodes = tuple(range(self.n_shards))
        if not self.multipod:
            if self.mesh_pods > 1:
                raise ValueError(
                    "a multi-pod mesh needs flow_home='hash': the legacy "
                    "'ingest' scheme homes every flow on its ingest shard "
                    "and would never exercise the cross-pod exchange")
            if cfg.ports_per_pod and cfg.ports_per_pod != self.n_shards:
                raise ValueError(
                    "flow_home='ingest' supports exactly one port per "
                    f"shard ({self.n_shards}), got ports_per_pod="
                    f"{cfg.ports_per_pod}")
            if cfg.reporter_slots and (cfg.reporter_slots
                                       != cfg.flows_per_shard):
                raise ValueError(
                    "flow_home='ingest' mints flow ids from the shard "
                    "range, so reporter_slots must equal flows_per_shard")
            self.total_ports = self.n_shards
            self.ports_per_device = 1
            self.rep_cfg = cfg
            self.port_capacity = 0
            self.stage1_capacity = 0
            self.stage2_capacity = 0
            self.crosspod_capacity = 0
            return
        if cfg.pods != self.mesh_pods:
            raise ValueError(
                f"cfg.pods={cfg.pods} does not match the mesh's pod "
                f"axis ({self.mesh_pods}): total_ports = mesh_pods x "
                "ports_per_pod, so a silent mismatch would change the "
                "port set (and every per-port table) out from under the "
                "config")
        total_ports = (self.mesh_pods * cfg.ports_per_pod
                       if cfg.ports_per_pod else self.n_shards)
        if total_ports % self.n_shards:
            raise ValueError(
                f"total ports ({self.mesh_pods} pods x "
                f"{cfg.ports_per_pod}/pod = {total_ports}) must be a "
                f"multiple of the device count {self.n_shards}")
        if total_ports > self.wire.n_reporters:
            # with more ports than reporter ids, two ports alias one id
            # and the home-side canonical (flow, reporter, seq) order —
            # and with it the pod-count-invariance contract — stops
            # being deterministic. Fail loud instead of silently
            # degrading; the cap is the schema's, not a constant: V1's
            # 8-bit field allows 256 ports, wire_format="v2" lifts it
            # to 65,536.
            raise ValueError(
                f"total ports {total_ports} exceeds the "
                f"{self.wire.reporter_width}-bit reporter id space of "
                f"wire format {self.wire.name!r} "
                f"({self.wire.n_reporters}); canonical report ordering "
                "requires a unique (flow, reporter) pair per period — "
                "set wire_format='v2' (or REPRO_WIRE_FORMAT=v2) for "
                "u16 reporter ids")
        self.total_ports = total_ports
        self.ports_per_device = total_ports // self.n_shards
        self.rep_cfg = (dataclasses.replace(
            cfg, flows_per_shard=cfg.reporter_table_slots())
            if cfg.reporter_slots else cfg)
        self.port_capacity = cfg.port_report_capacity or max(
            1, cfg.report_capacity // total_ports)
        # stage capacities (worst case: every report to one bucket); the
        # ragged exchange replaces stage 2's padded cap with a compact
        # per-destination segment size — 0/auto keeps the worst case, so
        # compaction is structurally drop-free and bitwise ≡ padded
        self.stage1_capacity = max(
            1, self.ports_per_device * self.port_capacity)
        self.stage2_capacity = self.shards_per_pod * self.stage1_capacity
        if cfg.crosspod_capacity > self.stage2_capacity:
            raise ValueError(
                f"crosspod_capacity={cfg.crosspod_capacity} exceeds the "
                f"worst-case stage-2 capacity {self.stage2_capacity} "
                "(shards_per_pod x stage-1 bucket) — a larger segment "
                "can never fill; this is a misconfiguration")
        if cfg.crosspod_capacity and self.crosspod_exchange != "ragged":
            raise ValueError(
                "crosspod_capacity only applies to "
                "crosspod_exchange='ragged' (the padded exchange always "
                "ships the worst-case buckets)")
        self.crosspod_capacity = (
            (cfg.crosspod_capacity or self.stage2_capacity)
            if self.crosspod_exchange == "ragged" else 0)

    # -- state ------------------------------------------------------------
    def init_state(self) -> DFAState:
        """Global state arrays. Translator/collector tables have leading
        dim = n_shards * per-shard size; the reporter side tiles one
        per-PORT table per port (total_ports == n_shards with one port per
        device, i.e. always in legacy mode)."""

        def tile(st, count):
            return jax.tree.map(
                lambda a: jnp.tile(a[None], (count,) + (1,) * a.ndim
                                   ).reshape((count * a.shape[0],)
                                             + a.shape[1:])
                if a.ndim >= 1 else jnp.tile(a[None], (count,)), st)

        n = self.n_shards
        return DFAState(tile(REP.init_state(self.rep_cfg),
                             self.total_ports),
                        tile(TRANS.init_state(self.cfg), n),
                        tile(COLL.init_state(self.cfg), n))

    def state_specs(self) -> DFAState:
        """PartitionSpecs: every leading dim sharded over the whole mesh."""
        ax = self.axes

        def spec(a):
            return P(ax, *([None] * (a.ndim - 1))) if a.ndim >= 1 else P()

        # build from abstract eval to avoid allocating:
        st = jax.eval_shape(self.init_state)
        return jax.tree.map(spec, st)

    def state_shardings(self) -> DFAState:
        return jax.tree.map(lambda s: NamedSharding(self.mesh, s),
                            self.state_specs())

    def init_sharded_state(self) -> DFAState:
        """``init_state`` already placed on the mesh. Use this when feeding
        a donated step/stream: plain ``init_state`` arrays are uncommitted,
        so the first donated call returns mesh-sharded state and the second
        call pays a full retrace."""
        return jax.jit(self.init_state,
                       out_shardings=self.state_shardings())()

    # -- the step (two half-steps) ----------------------------------------
    _METRIC_KEYS = ("reports_sent", "reports_due", "reports_recv",
                    "bucket_drops", "misroutes", "collisions",
                    "bad_checksum", "seq_anomalies", "lost_reports")

    @property
    def fault_spec(self) -> Optional[FAULTS.FaultSpec]:
        """The armed transport-fault schedule, or None (fault path
        compiled out — zero cost when no injector is configured)."""
        fs = self.cfg.fault_spec
        return fs if fs is not None and fs.armed else None

    def _metric_specs(self, ax) -> Dict[str, P]:
        specs = {k: P() for k in self._METRIC_KEYS}
        if self.multipod and self.crosspod_exchange == "ragged":
            # exchange-volume accounting exists only on the compact
            # path: emitting (nonzero) keys on the default padded path
            # would break the pinned golden fingerprints
            specs.update({"crosspod_sent": P(), "crosspod_messages": P()})
        if self.fault_spec is not None:
            specs.update({k: P() for k in FAULTS.COUNT_KEYS})
            specs.update({k: P(ax) for k in FAULTS.LEDGER_KEYS})
        return specs

    def ingest_half(self, state: DFAState, events: Dict[str, jax.Array],
                    now: jax.Array
                    ) -> Tuple[DFAState, RoutedBatch, Dict[str, jax.Array]]:
        """First half of one monitoring period: reporter ingest, due-flow
        reports, all_to_all routing, translator addressing and ring
        placement — everything that must happen at line rate.

        events (global): ts/size (n_shards*E,), five_tuple (…,5),
        valid (…,). Returns (state', routed, metrics): ``routed`` is the
        period's :class:`RoutedBatch` (what the enrich half consumes, now
        or a period later), ``metrics`` are all PER-PERIOD deltas — the
        cumulative collision/checksum/sequence counters live in the state;
        here each period reports only what it added.

        With ``cfg.flow_home == "hash"`` the body is the 2D (pod, shard)
        mesh variant: per-port reporter tables, hash-home flow ids, and
        the two-stage intra-pod/cross-pod exchange.
        """
        if self.multipod:
            return self._ingest_half_mesh2d(state, events, now)
        cfg = self.cfg
        n = self.n_shards
        cap_out = max(1, cfg.report_capacity // n)
        ax = self.axes

        def local(rep_st, tr_st, coll_st, ev_ts, ev_sz, ev_tu, ev_va, now_):
            shard = jnp.zeros((), jnp.int32)
            for a in ax:
                shard = shard * jax.lax.axis_size(a) + jax.lax.axis_index(a)
            flow_base = shard * cfg.flows_per_shard
            # cumulative counters BEFORE this period (for metric deltas)
            collisions0 = jnp.sum(rep_st.collisions)
            bad_csum0 = jnp.sum(coll_st.bad_checksum)
            seq_anom0 = jnp.sum(coll_st.seq_anomalies)
            lost0 = jnp.sum(coll_st.lost_reports)
            # 1. reporter ingest (ingest_update via the dispatch
            # registry: ref = multipass oracle, pallas/interpret = fused
            # sort-once kernel; cfg.ingest_variant/event_tile select the
            # event-stream memory strategy)
            with jax.named_scope("reporter"):
                with jax.named_scope("ingest"):
                    rep_st = REP.ingest(rep_st, {"ts": ev_ts, "size": ev_sz,
                                                 "five_tuple": ev_tu,
                                                 "valid": ev_va}, cfg)
                # 2. due flows -> DTA reports; ``due`` counts every due
                # flow, the ones past the capacity cut included
                with jax.named_scope("due"):
                    due = jnp.sum(REP.due_mask(rep_st, now_, cfg))
                    slots, mask = REP.due_flows(rep_st, now_, cfg,
                                                cfg.report_capacity)
                with jax.named_scope("reports"):
                    rep_st, reports = REP.make_reports(
                        rep_st, slots, mask, now_, 0, flow_base, cfg)
                    # reporter id = shard (mod the schema's reporter id
                    # space); repack through the schema — no open-coded
                    # shifts here
                    wf = self.wire
                    rid = (shard % wf.n_reporters).astype(jnp.uint32)
                    mw = wf.report_meta_word
                    reports = reports.at[:, mw].set(
                        jnp.where(mask,
                                  wf.set_report_reporter(reports[:, mw],
                                                         rid),
                                  0))
            # 3. route to owner shards (fixed-capacity buckets + all_to_all)
            with jax.named_scope("route"):
                buckets, bmask, mis = TRANS.route_reports(
                    reports, mask, n, cfg.flows_per_shard, cap_out)
                routed = jax.lax.all_to_all(buckets, ax, 0, 0, tiled=True)
                rmask = jax.lax.all_to_all(
                    bmask.astype(jnp.uint32), ax, 0, 0,
                    tiled=True).astype(bool)
                dropped = jnp.sum(mask) - jnp.sum(bmask) - mis
                routed = routed.reshape(n * cap_out, PROTO.REPORT_WORDS)
                rmask = rmask.reshape(n * cap_out)
            # 4. owner-side translator: history addresses + RoCEv2 payloads
            with jax.named_scope("translate"):
                tr_st, payloads, coords = TRANS.translate(
                    tr_st, routed, rmask, flow_base, cfg)
            # 5. collector ring placement (ring_scatter via dispatch),
            # optionally through the lossy-transport injector — faults
            # hit only what the collector sees (the RDMA segment);
            # routing coords stay faithful to what the switch emitted
            ing_pay, ing_mask = payloads, rmask
            fmetrics = {}
            if self.fault_spec is not None:
                with jax.named_scope("faults"):
                    ing_pay, ing_mask, fcounts, fledger = FAULTS.inject(
                        payloads, rmask, self.fault_spec, wf, now_, shard)
                    fmetrics = {k: jax.lax.psum(v, ax)
                                for k, v in fcounts.items()}
                    fmetrics.update(fledger)
            with jax.named_scope("collector"):
                lseq0, recv0 = coll_st.last_seq, coll_st.received
                coll_st = COLL.ingest(coll_st, ing_pay, ing_mask, flow_base,
                                      cfg)
                coll_st, lost_delta = _global_seq_gap(
                    coll_st, lseq0, recv0, lost0, shard, ax)
            metrics = {
                "reports_sent": jax.lax.psum(jnp.sum(mask), ax),
                "reports_due": jax.lax.psum(due, ax),
                "reports_recv": jax.lax.psum(jnp.sum(rmask), ax),
                "bucket_drops": jax.lax.psum(jnp.sum(dropped), ax),
                "misroutes": jax.lax.psum(mis, ax),
                # u32 new-minus-old is the period delta even across
                # counter wraparound
                "collisions": jax.lax.psum(
                    jnp.sum(rep_st.collisions) - collisions0, ax),
                "bad_checksum": jax.lax.psum(
                    jnp.sum(coll_st.bad_checksum) - bad_csum0, ax),
                "seq_anomalies": jax.lax.psum(
                    jnp.sum(coll_st.seq_anomalies) - seq_anom0, ax),
                "lost_reports": lost_delta,
                **fmetrics,
            }
            return (rep_st, tr_st, coll_st, coords["local_flow"],
                    routed[:, 0], rmask, metrics)

        specs = self.state_specs()
        ev_specs = (P(ax), P(ax), P(ax, None), P(ax))
        out_state_specs = (specs.reporter, specs.translator, specs.collector)
        fn = jax.shard_map(
            local, mesh=self.mesh,
            in_specs=(specs.reporter, specs.translator, specs.collector)
            + ev_specs + (P(),),
            out_specs=out_state_specs
            + (P(ax), P(ax), P(ax), self._metric_specs(ax)),
            check_vma=False)
        rep_st, tr_st, coll_st, local_flow, flow_id, rmask, metrics = fn(
            state.reporter, state.translator, state.collector,
            events["ts"], events["size"], events["five_tuple"],
            events["valid"], now)
        return (DFAState(rep_st, tr_st, coll_st),
                RoutedBatch(local_flow, flow_id, rmask), metrics)

    def _ingest_half_mesh2d(self, state: DFAState,
                            events: Dict[str, jax.Array], now: jax.Array
                            ) -> Tuple[DFAState, RoutedBatch,
                                       Dict[str, jax.Array]]:
        """The 2D (pod, shard) mesh ingest half (``flow_home == "hash"``).

        Per device (pod p, shard s):

          1. each hosted reporter PORT ingests its own event slice into
             its own Marina table (ports_per_device independent tables —
             the merged reporter state depends only on the port set, not
             on the mesh factorization);
          2. due flows per port -> DTA reports whose flow id is the
             HASH-HOME global id (translator.home_flow_ids of the stored
             key), reporter id = global port index;
          3. stage 1: bucket by home SHARD, all_to_all over the intra-pod
             shard fabric (reports now sit in their home pod-column);
          4. stage 2: bucket by home POD, exchange over the pod axis —
             only flows whose home pod differs from the ingest pod
             actually cross pods;
          5. the home translator canonically re-orders the received batch
             by (flow, reporter, seq) — making history-index assignment
             and ring placement independent of the exchange interleaving
             — then computes addresses and places payloads as in the 1D
             path.

        Stage capacities are sized to the worst case (every report to one
        bucket), so ``bucket_drops`` is structurally zero here; the
        per-stage drop accounting still feeds the metric so capacity
        experiments (smaller buckets = DTA's lossy trade) surface
        immediately.
        """
        cfg = self.cfg
        ax = self.axes
        wf = self.wire
        P_l = self.ports_per_device
        Rs = self.rep_cfg.flows_per_shard       # per-port table slots
        S = self.shards_per_pod
        pods = self.mesh_pods
        R_p = self.port_capacity
        cap1 = self.stage1_capacity             # stage-1 bucket capacity
        cap2 = self.stage2_capacity             # stage-2 bucket capacity
        ragged = self.crosspod_exchange == "ragged"
        cap2c = self.crosspod_capacity          # compact segment rows
        fps = cfg.flows_per_shard               # rings per device
        G = self.total_flows
        hrw = cfg.flow_home == "rendezvous"
        # the ref backend's per-port ingest is pure jnp (sort/scatter/
        # top_k — all with batching rules), so the hosted ports can run
        # under one vmap instead of a Python-unrolled loop; essential at
        # wide port counts (V2 meshes host hundreds of ports per device,
        # and an unrolled loop would compile one ingest body per port)
        vmap_ports = dispatch.resolve_backend(None, cfg) == "ref"
        # logical node roster (pod-major positions -> stable node ids);
        # replicated constant inside the shard_map closure
        nodes_arr = jnp.asarray(self.home_nodes, jnp.uint32)

        def local(rep_st, tr_st, coll_st, ev_ts, ev_sz, ev_tu, ev_va,
                  now_):
            if self.pod_axis is not None:
                pod = jax.lax.axis_index(self.pod_axis)
            else:
                pod = jnp.zeros((), jnp.int32)
            sp = jnp.zeros((), jnp.int32)
            for a in self.shard_axes:
                sp = sp * jax.lax.axis_size(a) + jax.lax.axis_index(a)
            dev = pod * S + sp
            if hrw:
                # flow ids encode the stable node id, not the position
                flow_base = (nodes_arr[dev]
                             * jnp.uint32(fps)).astype(jnp.int32)
            else:
                flow_base = dev * fps
            # cumulative counters BEFORE this period (for metric deltas)
            collisions0 = jnp.sum(rep_st.collisions)
            bad_csum0 = jnp.sum(coll_st.bad_checksum)
            seq_anom0 = jnp.sum(coll_st.seq_anomalies)
            lost0 = jnp.sum(coll_st.lost_reports)
            # per-port views of this device's reporter slice
            regs = rep_st.regs.reshape(P_l, Rs, REP.N_REG)
            last_ts = rep_st.last_ts.reshape(P_l, Rs)
            last_report = rep_st.last_report.reshape(P_l, Rs)
            keys = rep_st.keys.reshape(P_l, Rs, 5)
            active = rep_st.active.reshape(P_l, Rs)
            if ev_ts.shape[0] % P_l:
                raise ValueError(
                    f"per-device event count {ev_ts.shape[0]} must "
                    f"divide across {P_l} hosted ports — a truncated "
                    "split would silently drop trailing events and "
                    "shift every port's slice off the port-major trace "
                    "layout")
            E_p = ev_ts.shape[0] // P_l

            def port_body(pst, ev, gid):
                """One hosted port: ingest its event slice, emit its due
                reports. The global port id IS the reporter identity (mod
                the schema's reporter id space) — stable across mesh
                factorizations."""
                with jax.named_scope("ingest"):
                    pst = REP.ingest(pst, ev, self.rep_cfg)
                with jax.named_scope("due"):
                    due = jnp.sum(REP.due_mask(pst, now_, self.rep_cfg))
                    slots, mask = REP.due_flows(pst, now_, self.rep_cfg,
                                                R_p)
                with jax.named_scope("reports"):
                    rid = (gid % wf.n_reporters).astype(jnp.uint32)
                    if hrw:
                        fids = TRANS.rendezvous_flow_ids(
                            pst.keys[slots], nodes_arr, fps)
                    else:
                        fids = TRANS.home_flow_ids(pst.keys[slots], G)
                    pst, reports = REP.make_reports(
                        pst, slots, mask, now_, rid, 0, self.rep_cfg,
                        flow_ids=fids)
                return pst, reports, mask, due

            with jax.named_scope("reporter"):
                gids = dev * P_l + jnp.arange(P_l, dtype=jnp.int32)
                stacked = REP.ReporterState(regs, last_ts, last_report, keys,
                                            active, rep_st.seq,
                                            rep_st.collisions)
                ev_b = {"ts": ev_ts.reshape(P_l, E_p),
                        "size": ev_sz.reshape(P_l, E_p),
                        "five_tuple": ev_tu.reshape(P_l, E_p, 5),
                        "valid": ev_va.reshape(P_l, E_p)}
                if vmap_ports:
                    new_st, reports_s, masks_s, dues = jax.vmap(port_body)(
                        stacked, ev_b, gids)
                else:
                    # unrolled loop for the pallas/interpret backends: the
                    # ingest path can resolve to the scalar-prefetch HBM
                    # pallas variant, which has no batching rule; P_l stays
                    # small there (kernel meshes host single-digit ports)
                    outs = [port_body(jax.tree.map(lambda a: a[p], stacked),
                                      {k: v[p] for k, v in ev_b.items()},
                                      gids[p])
                            for p in range(P_l)]
                    new_st = jax.tree.map(lambda *xs: jnp.stack(xs),
                                          *[o[0] for o in outs])
                    reports_s = jnp.stack([o[1] for o in outs])
                    masks_s = jnp.stack([o[2] for o in outs])
                    dues = jnp.stack([o[3] for o in outs])
                rep_st = REP.ReporterState(
                    regs=new_st.regs.reshape(P_l * Rs, REP.N_REG),
                    last_ts=new_st.last_ts.reshape(P_l * Rs),
                    last_report=new_st.last_report.reshape(P_l * Rs),
                    keys=new_st.keys.reshape(P_l * Rs, 5),
                    active=new_st.active.reshape(P_l * Rs),
                    seq=new_st.seq,
                    collisions=new_st.collisions)
                reports = reports_s.reshape(P_l * R_p, wf.report_words)
                mask = masks_s.reshape(P_l * R_p)
                sent = jnp.sum(mask)
                due = jnp.sum(dues)
            # home-pod index from the flow word — a pure function, so
            # the ragged path can recompute it after its pre-merge sort
            if hrw:
                def hpod_of(fid):
                    return TRANS.node_position(
                        fid // jnp.uint32(fps), nodes_arr) // S
            else:
                def hpod_of(fid):
                    return TRANS.home_coords(fid, fps, S,
                                             self.n_shards)[0]
            with jax.named_scope("route"):
                # stage 1: intra-pod all_to_all by home shard. The shard
                # coordinate of even a corrupt flow id is in range (floor
                # mod), so misroutes surface at stage 2 via the pod
                # coordinate — mis1 is structurally zero and kept only so
                # the accounting stays stage-symmetric.
                if hrw:
                    pos1 = TRANS.node_position(
                        reports[:, 0] // jnp.uint32(fps), nodes_arr)
                    hshard = pos1 % S
                else:
                    _, hshard, _ = TRANS.home_coords(reports[:, 0], fps, S,
                                                     self.n_shards)
                b1, m1, mis1 = TRANS.route_by_dest(reports, mask, hshard, S,
                                                   cap1)
                drop1 = sent - jnp.sum(m1) - mis1
                if self.shard_axes:
                    b1 = jax.lax.all_to_all(b1, self.shard_axes, 0, 0,
                                            tiled=True)
                    m1 = jax.lax.all_to_all(
                        m1.astype(jnp.uint32), self.shard_axes, 0, 0,
                        tiled=True).astype(bool)
                r1 = b1.reshape(S * cap1, PROTO.REPORT_WORDS)
                m1 = m1.reshape(S * cap1)
            with jax.named_scope("exchange"):
                # stage 2: cross-pod exchange by home pod
                extra = {}
                if ragged:
                    # compact exchange: pod-local rows never cross, remote
                    # rows are pre-merged (flow-major) and packed into
                    # cap2c-row segments — only the occupied capacity moves
                    # over the scarce inter-pod link
                    (lrows, lmask, b2, m2, mis2,
                     nmsg) = TRANS.crosspod_compact(
                        r1, m1, pod, pods, cap2c, hpod_of, wire=wf)
                    crosspod_sent = jnp.sum(m2)
                    drop2 = (jnp.sum(m1) - jnp.sum(lmask) - crosspod_sent
                             - mis2)
                    if self.pod_axis is not None:
                        b2 = jax.lax.all_to_all(b2, self.pod_axis, 0, 0,
                                                tiled=True)
                        m2 = jax.lax.all_to_all(
                            m2.astype(jnp.uint32), self.pod_axis, 0, 0,
                            tiled=True).astype(bool)
                    routed = jnp.concatenate(
                        [lrows,
                         b2.reshape(pods * cap2c, PROTO.REPORT_WORDS)])
                    rmask = jnp.concatenate(
                        [lmask, m2.reshape(pods * cap2c)])
                    extra = {
                        "crosspod_sent": jax.lax.psum(crosspod_sent, ax),
                        "crosspod_messages": jax.lax.psum(nmsg, ax)}
                else:
                    b2, m2, mis2 = TRANS.route_by_dest(
                        r1, m1, hpod_of(r1[:, 0]), pods, cap2)
                    drop2 = jnp.sum(m1) - jnp.sum(m2) - mis2
                    if self.pod_axis is not None:
                        b2 = jax.lax.all_to_all(b2, self.pod_axis, 0, 0,
                                                tiled=True)
                        m2 = jax.lax.all_to_all(
                            m2.astype(jnp.uint32), self.pod_axis, 0, 0,
                            tiled=True).astype(bool)
                    routed = b2.reshape(pods * cap2, PROTO.REPORT_WORDS)
                    rmask = m2.reshape(pods * cap2)
            with jax.named_scope("translate"):
                # home-side canonical arrival order (mesh-shape independent:
                # the ragged path's local/received split and the padded
                # path's bucket interleaving both collapse to the same
                # (flow, reporter, seq) total order)
                routed, rmask = TRANS.canonical_order(routed, rmask, wire=wf)
                # owner-side translator + ring placement, as in the 1D path
                tr_st, payloads, coords = TRANS.translate(
                    tr_st, routed, rmask, flow_base, cfg)
            # optional lossy-transport injector on the collector-facing
            # stream only (see the 1D path for the rationale)
            ing_pay, ing_mask = payloads, rmask
            fmetrics = {}
            if self.fault_spec is not None:
                with jax.named_scope("faults"):
                    ing_pay, ing_mask, fcounts, fledger = FAULTS.inject(
                        payloads, rmask, self.fault_spec, wf, now_, dev)
                    fmetrics = {k: jax.lax.psum(v, ax)
                                for k, v in fcounts.items()}
                    fmetrics.update(fledger)
            with jax.named_scope("collector"):
                lseq0, recv0 = coll_st.last_seq, coll_st.received
                coll_st = COLL.ingest(coll_st, ing_pay, ing_mask, flow_base,
                                      cfg)
                coll_st, lost_delta = _global_seq_gap(
                    coll_st, lseq0, recv0, lost0, dev, ax)
            metrics = {
                "reports_sent": jax.lax.psum(sent, ax),
                "reports_due": jax.lax.psum(due, ax),
                "reports_recv": jax.lax.psum(jnp.sum(rmask), ax),
                "bucket_drops": jax.lax.psum(drop1 + drop2, ax),
                "misroutes": jax.lax.psum(mis1 + mis2, ax),
                **extra,
                "collisions": jax.lax.psum(
                    jnp.sum(rep_st.collisions) - collisions0, ax),
                "bad_checksum": jax.lax.psum(
                    jnp.sum(coll_st.bad_checksum) - bad_csum0, ax),
                "seq_anomalies": jax.lax.psum(
                    jnp.sum(coll_st.seq_anomalies) - seq_anom0, ax),
                "lost_reports": lost_delta,
                **fmetrics,
            }
            return (rep_st, tr_st, coll_st, coords["local_flow"],
                    routed[:, 0], rmask, metrics)

        specs = self.state_specs()
        ev_specs = (P(ax), P(ax), P(ax, None), P(ax))
        out_state_specs = (specs.reporter, specs.translator,
                           specs.collector)
        fn = jax.shard_map(
            local, mesh=self.mesh,
            in_specs=(specs.reporter, specs.translator, specs.collector)
            + ev_specs + (P(),),
            out_specs=out_state_specs
            + (P(ax), P(ax), P(ax), self._metric_specs(ax)),
            check_vma=False)
        rep_st, tr_st, coll_st, local_flow, flow_id, rmask, metrics = fn(
            state.reporter, state.translator, state.collector,
            events["ts"], events["size"], events["five_tuple"],
            events["valid"], now)
        return (DFAState(rep_st, tr_st, coll_st),
                RoutedBatch(local_flow, flow_id, rmask), metrics)

    def enrich_half(self, state: DFAState, routed: RoutedBatch):
        """Second half of a monitoring period: history gather +
        enrichment of the routed flows (via dispatch; the op owns the
        [0, F) clamp of local_flow and the memory-strategy choice — the
        ring pinned in VMEM while it fits, else an XLA gather of the R
        routed rows feeding the derive kernel), plus the optional
        immediate-inference hook on the resulting features.

        Reads the collector ring, never writes it — which is what makes
        it legal to defer one period in the overlapped driver. Returns
        (enriched (R, D), flow_ids (R,), emask (R,), preds) where preds
        is None unless an inference head is armed.
        """
        cfg = self.cfg
        ax = self.axes

        def local(coll_st, lf, fid, m):
            with jax.named_scope("enrich"):
                enriched = COLL.enrich_flow_history(coll_st, lf, cfg,
                                                    mask=m)
                flow_ids = jnp.where(m, fid, jnp.uint32(WIRE.PAD_FLOW_ID))
            return enriched, flow_ids, m

        specs = self.state_specs()
        fn = jax.shard_map(
            local, mesh=self.mesh,
            in_specs=(specs.collector, P(ax), P(ax), P(ax)),
            out_specs=(P(ax, None), P(ax), P(ax)), check_vma=False)
        enriched, flow_ids, emask = fn(state.collector, routed.local_flow,
                                       routed.flow_id, routed.mask)
        preds = None
        if self.infer_fn is not None:
            # the hook consumes the features in the same trace — "features
            # land in device memory and are consumed in the same period"
            with jax.named_scope("enrich"), jax.named_scope("infer"):
                preds = self.infer_fn(enriched)
                preds = jnp.where(emask[:, None], preds, 0.0)
        return enriched, flow_ids, emask, preds

    def dfa_step(self, state: DFAState, events: Dict[str, jax.Array],
                 now: jax.Array) -> StepOutputs:
        """One full monitoring period = ingest_half ∘ enrich_half.

        events (global): ts/size (n_shards*E,), five_tuple (…,5),
        valid (…,). Returns :class:`StepOutputs` (``preds`` is ``None``
        unless an inference head is armed — the arity never changes)."""
        state, routed, metrics = self.ingest_half(state, events, now)
        enriched, flow_ids, emask, preds = self.enrich_half(state, routed)
        return StepOutputs(state, enriched, flow_ids, emask, metrics,
                           preds)

    # -- multi-period streaming -------------------------------------------
    def run_periods(self, state: DFAState, events: Dict[str, jax.Array],
                    nows: jax.Array) -> StepOutputs:
        """Stream T monitoring periods, each a full ingest+enrich chain,
        as one ``lax.scan`` (state is the carry, so with donation the ring
        memory is updated in place across the whole scan — the GDR
        analogue held for an entire trace window).

        events: dict of (T, n_shards*E, …) arrays; nows: (T,) u32.
        Returns :class:`StepOutputs` with the per-period fields stacked
        under a leading (T,) dim (metrics values are (T,) PER-PERIOD
        arrays; ``preds`` is (T, R, C) or ``None``).
        """

        def body(st, xs):
            ev, now_ = xs
            st, routed, metrics = self.ingest_half(st, ev, now_)
            enriched, flow_ids, emask, preds = self.enrich_half(st, routed)
            return st, (enriched, flow_ids, emask, metrics, preds)

        state, (enriched, flow_ids, emask, metrics, preds) = jax.lax.scan(
            body, state, (events, nows))
        return StepOutputs(state, enriched, flow_ids, emask, metrics,
                           preds)

    def run_periods_overlapped(self, state: DFAState,
                               events: Dict[str, jax.Array],
                               nows: jax.Array) -> StepOutputs:
        """Software-pipelined stream: period t's enrich(+inference) half
        runs in the same scan body as period t+1's ingest half, so
        enrichment latency overlaps the next period's line-rate work
        instead of serializing against it (ROADMAP: "overlapped
        ingest/enrich, double-buffered periods").

        The scan carry is (state, RoutedBatch of the previous period); the
        body first enriches the carried coords — reading the ring BEFORE
        this body's placement touches it — then ingests the new period.
        One warm-up ingest precedes the scan, one drain enrich follows it.
        Output-identical to ``run_periods`` (the equivalence is exact, not
        approximate: same reads of the same ring states in both drivers);
        T=1 degenerates to warm-up + drain with a zero-length scan.

        Same signature and returns as ``run_periods``.
        """
        ev0 = {k: v[0] for k, v in events.items()}
        state, routed0, metrics0 = self.ingest_half(state, ev0, nows[0])

        def body(carry, xs):
            st, prev = carry
            ev, now_ = xs
            # enrich period t from the pre-ingest ring (sequential
            # semantics) while ingesting period t+1
            enriched, flow_ids, emask, preds = self.enrich_half(st, prev)
            st, routed, metrics = self.ingest_half(st, ev, now_)
            return (st, routed), (enriched, flow_ids, emask, metrics,
                                  preds)

        rest = ({k: v[1:] for k, v in events.items()}, nows[1:])
        (state, last), (enriched, flow_ids, emask, metrics, preds) = (
            jax.lax.scan(body, (state, routed0), rest))
        # drain: the final period's enrich half
        enr_t, fid_t, em_t, preds_t = self.enrich_half(state, last)

        def tail(stacked, last_row):
            return jnp.concatenate([stacked, last_row[None]], axis=0)

        enriched = tail(enriched, enr_t)
        flow_ids = tail(flow_ids, fid_t)
        emask = tail(emask, em_t)
        preds = None if preds_t is None else tail(preds, preds_t)
        # the warm-up produced period 0's metrics; the scan periods 1..T-1
        metrics = jax.tree.map(
            lambda m0, m: jnp.concatenate([m0[None], m], axis=0),
            metrics0, metrics)
        return StepOutputs(state, enriched, flow_ids, emask, metrics,
                           preds)

    # -- convenience ------------------------------------------------------
    def describe(self) -> Dict[str, Any]:
        """Trace-time kernel selection for this system: backend, gather
        memory strategy, ingest event-stream strategy, and the VMEM
        numbers that drove the choices."""
        from repro.kernels.ingest_update.kernel import clamp_tile
        cfg = self.cfg
        backend = dispatch.resolve_backend(None, cfg)
        # mirror the ingest half: each shard enriches R routed rows, and
        # ops.gather_enrich tiles that R by flow_tile
        if self.multipod:
            R = self.total_ports * self.port_capacity
        else:
            R = self.n_shards * max(1, cfg.report_capacity
                                    // self.n_shards)
        tile = min(dispatch.resolve_report_tile(cfg, R), R)
        variant = ("ref" if backend == "ref" else
                   dispatch.resolve_gather_variant(
                       None, cfg, cfg.flows_per_shard, cfg.history, tile,
                       cfg.derived_dim))
        # ingest side: each shard sorts/reduces event_block events/period
        etile = clamp_tile(
            dispatch.resolve_event_tile(cfg, cfg.event_block),
            cfg.event_block)
        ingest_variant = ("ref" if backend == "ref" else
                          dispatch.resolve_ingest_variant(
                              None, cfg, cfg.event_block, etile))
        return {
            "kernel_backend": backend,
            "wire_format": self.wire.name,
            "gather_variant": variant,
            "ingest_variant": ingest_variant,
            "event_tile": etile,
            "ingest_vmem_bytes": dispatch.ingest_vmem_bytes(
                "hbm" if ingest_variant == "hbm" else "block",
                cfg.event_block, etile),
            "ring_region_bytes": cfg.ring_region_bytes(),
            "vmem_budget_bytes": cfg.vmem_budget_mb
            * dispatch.VMEM_BYTES_PER_MB,
            "gather_vmem_bytes": dispatch.gather_vmem_bytes(
                "hbm" if variant == "hbm" else "full",
                cfg.flows_per_shard, cfg.history, tile, cfg.derived_dim,
                words=cfg.payload_words),
            "n_shards": self.n_shards,
            "flow_home": cfg.flow_home,
            "pods": self.mesh_pods,
            "shards_per_pod": self.shards_per_pod,
            "total_ports": self.total_ports,
            "ports_per_device": self.ports_per_device,
            "reporter_slots": self.rep_cfg.flows_per_shard,
            "port_report_capacity": self.port_capacity,
            # stage-2 exchange strategy (crosspod_capacity is the
            # per-destination segment size the ragged path ships;
            # stage2_capacity is what the padded path would ship)
            "crosspod_exchange": self.crosspod_exchange,
            "crosspod_capacity": self.crosspod_capacity,
            "stage2_capacity": self.stage2_capacity,
            "tuning_registry": TUNING.resolve_path(cfg) or "none",
            # elastic knobs (launch.elastic reads the same fields)
            "home_nodes": self.home_nodes,
            "snapshot_every_periods": cfg.snapshot_every_periods,
            "overlap_periods": cfg.overlap_periods,
            "inference_head": ("custom" if (self.infer_fn is not None
                                            and self.infer_params is None)
                               else cfg.inference_head),
            # serving knobs (launch.serving reads the same fields)
            "serve_offered_eps": cfg.serve_offered_eps,
            "serve_budget_us": cfg.serve_budget_resolved_us(),
            "serve_queue_events": cfg.serve_queue_events,
            "drop_policy": cfg.drop_policy,
            # transport-fault / elastic robustness knobs
            "fault_injection": (self.fault_spec.describe()
                                if self.fault_spec is not None else "none"),
            "rehome_collision_policy": cfg.rehome_collision_policy,
        }

    def jit_step(self, donate: bool = True):
        """jit'd single-period step, cached per donate flag (the serving
        loop warms up and then serves through the SAME compiled step)."""
        cache = getattr(self, "_step_jits", None)
        if cache is None:
            cache = self._step_jits = {}
        if bool(donate) not in cache:
            cache[bool(donate)] = jax.jit(
                self.dfa_step, donate_argnums=(0,) if donate else ())
        return cache[bool(donate)]

    def jit_stream(self, donate: bool = True,
                   overlapped: Optional[bool] = None):
        """jit'd streaming driver with the state carry donated.

        ``overlapped`` defaults to ``cfg.overlap_periods``; the two
        drivers are output-identical, so callers pick purely on latency
        shape. The jitted callable is cached per (overlapped, donate), so
        repeated lookups share one trace."""
        if overlapped is None:
            overlapped = self.cfg.overlap_periods
        key = (bool(overlapped), bool(donate))
        cache = getattr(self, "_stream_jits", None)
        if cache is None:
            cache = self._stream_jits = {}
        if key not in cache:
            fn = (self.run_periods_overlapped if overlapped
                  else self.run_periods)
            cache[key] = jax.jit(fn,
                                 donate_argnums=(0,) if donate else ())
        return cache[key]

    def stream(self, state: DFAState, events: Dict[str, jax.Array],
               nows: jax.Array, overlapped: Optional[bool] = None,
               donate: bool = False,
               snapshot_dir: Optional[str] = None,
               snapshot_start: int = 0) -> StepOutputs:
        """THE streaming entry point: run T monitoring periods and return
        :class:`StepOutputs`, dispatching between the sequential and the
        software-pipelined driver (``overlapped`` defaults to
        ``cfg.overlap_periods`` — the two are output-identical, so the
        knob is purely a latency-shape choice).

        Subsumes the jit_stream/run_periods* juggling at call sites: one
        call, one structured return, jit caches shared across calls.
        ``donate=True`` donates the state carry (the caller must not
        reuse the passed-in state afterwards — streaming-loop shape).

        With ``cfg.snapshot_every_periods > 0`` and a snapshot directory
        (``snapshot_dir`` argument, else ``cfg.snapshot_dir``), the trace
        runs in chunks of that many periods with an async full-DFAState
        checkpoint at each chunk boundary AND after the final (possibly
        partial) chunk — so the on-disk replay window is at most
        ``snapshot_every_periods``. Checkpoint steps are GLOBAL period
        indices, offset by ``snapshot_start`` (pass the restored period
        when resuming after a recovery). The chunked run is bitwise
        identical to the unchunked one (pinned in tests): snapshotting is
        pure observation, ``checkpoint.save`` copies to host before the
        next chunk touches the carry."""
        every = int(self.cfg.snapshot_every_periods)
        sdir = snapshot_dir if snapshot_dir is not None \
            else (self.cfg.snapshot_dir or None)
        if every <= 0 or sdir is None:
            return self.jit_stream(donate=donate, overlapped=overlapped)(
                state, events, nows)
        return self._stream_snapshotted(state, events, nows, overlapped,
                                        donate, sdir, every,
                                        int(snapshot_start))

    def _stream_snapshotted(self, state, events, nows, overlapped,
                            donate, sdir, every, start):
        from repro.checkpoint import checkpoint as CKPT
        T = int(nows.shape[0])
        outs = []
        threads = []
        for lo in range(0, T, every):
            hi = min(lo + every, T)
            ev = {k: v[lo:hi] for k, v in events.items()}
            # chunk 0 honors the caller's donate contract; the internal
            # carry is always ours to donate
            out = self.jit_stream(donate=donate if lo == 0 else True,
                                  overlapped=overlapped)(
                state, ev, nows[lo:hi])
            state = out.state
            # async snapshot: save() device_gets synchronously (the carry
            # is safe to donate to the next chunk), only the file IO rides
            # the background thread
            t = CKPT.save(state, sdir, step=start + hi,
                          keep=self.cfg.snapshot_keep, async_=True)
            if t is not None:
                threads.append(t)
            outs.append(out)
        for t in threads:
            t.join()
        if len(outs) == 1:
            return outs[0]
        stacked = jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0),
                               *[o._replace(state=None, preds=None)
                                 for o in outs])
        preds = (None if outs[0].preds is None else
                 jnp.concatenate([o.preds for o in outs], axis=0))
        return stacked._replace(state=state, preds=preds)

    def event_specs(self, events_per_shard: int, periods: int = 0):
        """ShapeDtypeStructs + shardings for the global event batch; with
        ``periods`` > 0, shapes carry the leading (T,) streaming dim."""
        n = self.n_shards * events_per_shard
        lead = (periods,) if periods else ()
        sds = {
            "ts": jax.ShapeDtypeStruct(lead + (n,), jnp.uint32),
            "size": jax.ShapeDtypeStruct(lead + (n,), jnp.uint32),
            "five_tuple": jax.ShapeDtypeStruct(lead + (n, 5), jnp.uint32),
            "valid": jax.ShapeDtypeStruct(lead + (n,), jnp.bool_),
        }
        ax = self.axes
        t = (None,) if periods else ()
        specs = {"ts": P(*t, ax), "size": P(*t, ax),
                 "five_tuple": P(*t, ax, None), "valid": P(*t, ax)}
        return sds, specs
