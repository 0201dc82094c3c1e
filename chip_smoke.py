"""Smoke check of the DFA serving path on a TPU, with compiled kernels.

    python chip_smoke.py              # one chip (default)
    python chip_smoke.py --chips 4    # the (pod, shard) mesh path only

One chip: ``DFASystem(PAPER, kernel_backend="pallas")`` on a (1, 1) mesh
serves a seeded trace through ``launch/serving.py`` (``build_source`` +
``ServingLoop.run``). The script checks the serving report, that the
compiled step holds the Pallas kernels of the main path, and then, in the
same process, replays the trace with ``kernel_backend="ref"``: integer
state must agree bit for bit, and in every period the collector's ring and
validity bitwise and the enriched features within the per-row tolerance of
``tests/test_gather_enrich_equiv.py``. Then ``ring_scatter`` alone at the
PAPER shapes (F = 2^17, R = 65,536) on adversarial coordinates — repeated
(flow, hist), every report in one tile, masked rows at the ring's edges —
must equal ``ring_scatter_ref`` (run on the host's CPU, where a scatter
applies its updates in order) bit for bit. Last, the enrichment's
division (``enrich.div_rn``, in a kernel and in XLA) must equal IEEE
division, computed by numpy on the host, bit for bit.

``--chips 4``: one seeded trace streams through a (2, 2)
``make_dfa_mesh`` system (``flow_home="hash"``, PAPER per-shard sizes),
with the padded and with the ragged stage-2 exchange, and through a
(1, 1) mesh on one of the four chips holding the global keyspace fixed.
The canonically re-gathered end state and every per-period metric must
match bit for bit.

The last line of stdout is ``{"ok": true, "device": {...}}``. Without a
TPU, or outside a checkout of this repository, the script exits non-zero
and prints no result. Latencies it prints come from one smoke run and
are not a measurement.
"""
from __future__ import annotations

import argparse
import concurrent.futures
import dataclasses
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

CACHE_DIR = enable_compile_cache()

import jax  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding  # noqa: E402

from repro.compat import make_mesh  # noqa: E402
from repro.configs.dfa import PAPER  # noqa: E402
from repro.core.pipeline import DFASystem  # noqa: E402
from repro.data import packets as PK  # noqa: E402
from repro.kernels import dispatch  # noqa: E402
from repro.launch.mesh import make_dfa_mesh  # noqa: E402
from repro.launch.serving import ServingLoop, build_source  # noqa: E402

FEATURE_TOL = 1e-5        # per-row scale, as in test_gather_enrich_equiv
N_FLOWS = 4096
PERIODS = 16
SEED = 0
MAIN_PATH_KERNELS = ("ingest_update", "ring_scatter", "gather_enrich_hbm")
SCATTER_REPORTS = 65536   # the benchmark's report_capacity
MESH_PORTS = 4            # reporter ports of the mesh run: 1 per chip
MESH_SHARDS = 4           # the (2, 2) mesh; G = 4 x PAPER per-shard flows


def log(msg: str) -> None:
    print(msg, flush=True)


def require_tpu(n: int):
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU (platform "
                         f"{devices[0].platform!r})")
    if len(devices) < n:
        raise SystemExit(f"chip_smoke: needs {n} chips, JAX found "
                         f"{len(devices)}")
    return devices


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(f"chip_smoke: {what}")


def trace(n_ports: int, events_per_port: int):
    """Seeded (PERIODS, n_ports * events_per_port, ...) trace, port-major."""
    return PK.period_batches(n_ports, PERIODS, events_per_port,
                             n_flows=N_FLOWS, flow_seed=SEED,
                             period_us=PAPER.monitoring_period_us)


def state_specs(system):
    """ShapeDtypeStructs of the system's sharded state (no allocation)."""
    return jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        jax.eval_shape(system.init_state), system.state_shardings())


def event_structs(system, events_per_shard: int, periods: int = 0):
    sds, specs = system.event_specs(events_per_shard, periods)
    return {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=NamedSharding(
        system.mesh, specs[k])) for k, v in sds.items()}


def host_tree(tree):
    return jax.tree.map(np.asarray, jax.device_get(tree))


def assert_integer_state_equal(want, got, ctx: str) -> None:
    flat_w = jax.tree_util.tree_flatten_with_path(want)[0]
    flat_g = jax.tree.leaves(got)
    for (path, w), g in zip(flat_w, flat_g):
        name = jax.tree_util.keystr(path)
        check(np.issubdtype(w.dtype, np.integer) or w.dtype == bool,
              f"{ctx}: state leaf {name} is {w.dtype}, not integer")
        check(np.array_equal(w, g), f"{ctx}: state {name} differs")


def assert_rows_close(got, want, ctx: str) -> float:
    """max |got - want| per row <= FEATURE_TOL x that row's scale, over
    the finite features; a non-finite feature (the window std of a skew
    that overflows f32) must be the same value in both."""
    finite = np.isfinite(want)
    check(np.array_equal(finite, np.isfinite(got))
          and np.array_equal(got[~finite], want[~finite]),
          f"{ctx}: non-finite features differ")
    got, want = np.where(finite, got, 0.0), np.where(finite, want, 0.0)
    scale = np.maximum(1.0, np.abs(want).max(axis=-1, keepdims=True))
    rel = np.abs(got - want) / scale
    err = float(rel.max(initial=0.0))
    if err > FEATURE_TOL:
        r, c = np.unravel_index(rel.argmax(), rel.shape)
        check(False, f"{ctx}: features differ by {err:.3e} of the row "
              f"scale; worst at column {c}: {got[r, c]!r} vs "
              f"{want[r, c]!r}, row scale {float(scale[r, 0])!r}")
    return err


# -- one chip -----------------------------------------------------------------

class PeriodTap:
    """The system, with every period's ring, validity and outputs copied
    to the host before the next (donating) step consumes the state."""

    def __init__(self, inner):
        self._inner = inner
        self.periods = []

    def jit_step(self, donate: bool = True):
        step = self._inner.jit_step(donate)

        def tapped(state, events, now):
            out = step(state, events, now)
            coll = out.state.collector
            self.periods.append(host_tree({
                "memory": coll.memory, "entry_valid": coll.entry_valid,
                "mask": out.mask, "flow_ids": out.flow_ids,
                "enriched": out.enriched}))
            return out
        return tapped

    def __getattr__(self, name):
        return getattr(self._inner, name)


def serve(system, events, nows, warm: bool, tap=None):
    source = build_source(system, events, nows)
    loop = ServingLoop(tap or system, source)
    if warm:
        # one step outside the served window, so the loop's latency
        # samples hold no compile (the jit is the one the loop uses)
        batch, now, _ = build_source(system, events, nows).next_batch()
        warm_out = system.jit_step(donate=True)(
            system.init_sharded_state(), *loop.ring.stage(batch, now))
        jax.block_until_ready(warm_out)
    return loop.run(PERIODS)


def one_chip() -> None:
    devices = require_tpu(1)
    mesh = make_mesh((1, 1), ("data", "model"), devices=devices[:1])
    cfg = dataclasses.replace(PAPER, kernel_backend="pallas",
                              serve_offered_eps=0.0)
    system = DFASystem(cfg, mesh)
    desc = system.describe()
    log("backend: " + json.dumps({k: desc[k] for k in (
        "kernel_backend", "gather_variant", "ingest_variant", "event_tile",
        "wire_format", "ring_region_bytes")}))
    check(desc["kernel_backend"] == "pallas", "backend did not resolve "
          "to pallas")
    check(desc["gather_variant"] == "hbm", "PAPER gather variant is not "
          "hbm")
    log(f"compile cache: {CACHE_DIR}")

    t0 = time.perf_counter()
    compiled = system.jit_step(donate=True).lower(
        state_specs(system), event_structs(system, cfg.event_block),
        jax.ShapeDtypeStruct((), np.uint32, sharding=NamedSharding(
            mesh, jax.sharding.PartitionSpec()))).compile()
    log(f"compile_s (pallas step, cache may hit): "
        f"{time.perf_counter() - t0:.3f}")
    kernels = dispatch.tpu_kernels(compiled.as_text())
    log(f"tpu_custom_call kernels: {kernels}")
    for k in MAIN_PATH_KERNELS:
        check(any(n.startswith(k) for n in kernels),
              f"no tpu_custom_call for {k} in the compiled step")
    log("tpu_custom_call found for " + ", ".join(MAIN_PATH_KERNELS))

    events, nows = trace(1, cfg.event_block)
    tap = PeriodTap(system)
    rep = serve(system, events, nows, warm=True, tap=tap)
    last = rep.last
    stats = devices[0].memory_stats() or {}
    log(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use', 'n/a')}")
    lat = rep.latency
    log(f"period latency, one smoke run, not a measurement: "
        f"p50={lat['p50']:.1f}us p99={lat['p99']:.1f}us "
        f"count={lat['count']}")
    metrics = host_tree(last.metrics)
    state = host_tree(last.state)
    mask = np.asarray(last.mask)
    enriched = np.asarray(last.enriched)
    log(f"report: periods={rep.periods} offered={rep.offered} "
        f"processed={rep.processed} dropped={rep.dropped} "
        f"reports_sent={int(metrics['reports_sent'])} "
        f"features={int(mask.sum())} "
        f"bad_checksum={int(state.collector.bad_checksum.sum())}")
    check(rep.balanced, "serving report does not balance")
    check(int(metrics["reports_sent"]) > 0, "no reports sent")
    check(int(mask.sum()) > 0, "no feature vectors delivered")
    check(int(state.collector.bad_checksum.sum()) == 0, "bad checksums")
    check(not np.isnan(enriched[mask]).any(), "NaN features")

    ref_system = DFASystem(dataclasses.replace(cfg, kernel_backend="ref"),
                           mesh)
    ref_tap = PeriodTap(ref_system)
    ref = serve(ref_system, events, nows, warm=False, tap=ref_tap)
    check((ref.offered, ref.processed, ref.dropped)
          == (rep.offered, rep.processed, rep.dropped),
          "ref replay saw different event accounting")
    assert_integer_state_equal(host_tree(ref.last.state), state,
                               "pallas vs ref")
    ref_metrics = host_tree(ref.last.metrics)
    for k in ref_metrics:
        check(np.array_equal(ref_metrics[k], metrics[k]),
              f"pallas vs ref: metric {k} differs")
    check(np.array_equal(np.asarray(ref.last.mask), mask),
          "pallas vs ref: report masks differ")
    check(np.array_equal(np.asarray(ref.last.flow_ids),
                         np.asarray(last.flow_ids)),
          "pallas vs ref: flow ids differ")
    err = assert_rows_close(enriched[mask],
                            np.asarray(ref.last.enriched)[mask],
                            "pallas vs ref")
    check(len(tap.periods) == len(ref_tap.periods) >= PERIODS,
          f"periods served: pallas {len(tap.periods)}, ref "
          f"{len(ref_tap.periods)}")
    for t, (got, want) in enumerate(zip(tap.periods, ref_tap.periods)):
        for k in ("memory", "entry_valid", "mask", "flow_ids"):
            off = int((got[k] != want[k]).sum())
            check(off == 0, f"pallas vs ref: period {t} {k}: {off} "
                  f"words differ")
        m = want["mask"]
        err = max(err, assert_rows_close(got["enriched"][m],
                                         want["enriched"][m],
                                         f"pallas vs ref, period {t}"))
    log(f"pallas == ref: integer state bitwise, last-period metrics "
        f"bitwise; in each of {len(tap.periods)} periods ring, validity, "
        f"masks and flow ids bitwise, features within {err:.3e} of the "
        f"row scale (tolerance {FEATURE_TOL:g})")
    ring_scatter_adversarial()
    division_rounding()


def division_rounding() -> None:
    """The enrichment's divisions on the chip against IEEE division
    (numpy on the host), bit for bit, on operands like the features':
    integer sums over counts, tiny moments over EPS, sums of squares
    near the largest float over the window's entry count. The chip's
    native quotient is counted too, for the record."""
    from jax.experimental import pallas as pl

    from repro.core import enrich as E
    rng = np.random.default_rng(SEED)
    n = 1 << 20
    s = rng.integers(0, 2**32, size=n).astype(np.float32)
    cnt = rng.integers(1, 2**20, size=n).astype(np.float32)
    m3 = (rng.standard_normal(n) * 1e28).astype(np.float32)
    big = (np.float32(3.4e38) * rng.random(n)).astype(np.float32)
    nv = rng.integers(1, 11, size=n).astype(np.float32)
    a = np.concatenate([s, m3, big]).reshape(-1, 1024)
    b = np.concatenate([cnt, np.full(n, E.EPS, np.float32), nv]
                       ).reshape(-1, 1024)
    want = (a / b).view(np.int32)

    def kernel(a_ref, b_ref, rn_ref, native_ref):
        rn_ref[...] = E.div_rn(a_ref[...], b_ref[...])
        native_ref[...] = a_ref[...] / b_ref[...]

    spec = pl.BlockSpec((32, 1024), lambda i: (i, 0))
    rn, native = jax.jit(lambda a, b: pl.pallas_call(
        kernel, grid=(a.shape[0] // 32,), in_specs=[spec, spec],
        out_specs=[spec, spec],
        out_shape=[jax.ShapeDtypeStruct(a.shape, np.float32)] * 2,
        interpret=dispatch.interpret_flag("pallas"),
        name="division_rounding")(a, b))(a, b)
    xla = jax.jit(E.div_rn)(a, b)
    off = {k: int((np.asarray(v).view(np.int32) != want).sum())
           for k, v in (("kernel", rn), ("xla", xla), ("native", native))}
    check(off["kernel"] == 0 and off["xla"] == 0,
          f"div_rn differs from IEEE division: {off}")
    log(f"div_rn == IEEE division bitwise on {want.size} quotients, in a "
        f"kernel and in XLA; the native kernel quotient differs in "
        f"{off['native']}")


def ring_scatter_adversarial() -> None:
    """``ring_scatter`` compiled at the PAPER ring and the benchmark's
    report count against the plain scatter, on coordinates that stress
    report order and tile ranges."""
    from repro.kernels.ring_scatter.kernel import ring_scatter_pallas
    from repro.kernels.ring_scatter.ref import ring_scatter_ref
    F, H, tile = PAPER.flows_per_shard, PAPER.history, PAPER.flow_tile
    R = SCATTER_REPORTS
    rng = np.random.default_rng(SEED)
    mem = rng.integers(0, 2**32, size=(F, H, 16),
                       dtype=np.uint64).astype(np.uint32)
    few = rng.choice(F * H, size=1000, replace=False)    # repeated coords
    edge = np.where(rng.random(R) > 0.5, 0, F - 1)       # clipped flows
    half = rng.random(R) > 0.5
    pick = few[rng.integers(0, few.size, size=R)]
    cases = {
        "repeats": (pick // H, pick % H, np.ones(R, bool)),
        "one_tile": (F // 2 + rng.integers(0, tile, size=R),
                     rng.integers(0, H, size=R), rng.random(R) > 0.1),
        "masked_edges": (np.where(half, pick // H, edge), pick % H, half),
    }
    scatter = jax.jit(functools.partial(
        ring_scatter_pallas, flow_tile=tile, history=H,
        interpret=dispatch.interpret_flag("pallas")))
    cpu = jax.devices("cpu")[0]
    for name, (flow, hist, mask) in cases.items():
        flow, hist = flow.astype(np.int32), hist.astype(np.int32)
        pay = rng.integers(0, 2**32, size=(R, 16),
                           dtype=np.uint64).astype(np.uint32)
        got = np.asarray(scatter(mem, pay, flow, hist, mask))
        with jax.default_device(cpu):
            want = np.asarray(ring_scatter_ref(*map(
                jax.numpy.asarray, (mem, pay, flow, hist, mask))))
        off = int((got != want).sum())
        check(off == 0, f"ring_scatter {name}: {off} words differ from "
              f"ring_scatter_ref")
        log(f"ring_scatter {name} (F={F}, R={R}, {int(mask.sum())} "
            f"unmasked) == ring_scatter_ref bitwise")


# -- four chips: the (pod, shard) mesh ----------------------------------------

def mesh_cfg(pods: int, shards: int, exchange: str):
    """PAPER per-shard sizes on a (pods, shards) mesh, global keyspace
    G = MESH_SHARDS x PAPER.flows_per_shard fixed across meshes. V2 wire
    ids: at this report rate a port's 8-bit V1 seq wraps within a period,
    past which the per-device duplicate window is legitimately
    mesh-dependent (tests/test_multipod_equiv.py states the bound)."""
    n = pods * shards
    return dataclasses.replace(
        PAPER, kernel_backend="pallas", flow_home="hash", pods=pods,
        ports_per_pod=MESH_PORTS // pods, wire_format="v2",
        reporter_slots=PAPER.flows_per_shard,
        flows_per_shard=PAPER.flows_per_shard * MESH_SHARDS // n,
        crosspod_exchange=exchange)


def merged_state(system, state) -> dict:
    """Mesh-shape-independent view of a DFAState (the canonical
    re-gather of tests/test_multipod_equiv.py)."""
    st = host_tree(state)
    n = system.n_shards
    out = {f"rep.{k}": a for k, a in st.reporter._asdict().items()}
    out["tr.hist_counter"] = st.translator.hist_counter
    c = st.collector
    out["coll.memory"] = c.memory
    out["coll.entry_valid"] = c.entry_valid
    out["coll.last_seq"] = c.last_seq.reshape(n, -1).max(0)
    for k in ("bad_checksum", "seq_anomalies", "received", "lost_reports"):
        out[f"coll.{k}"] = getattr(c, k).astype(np.uint64).sum()
    return out


def four_chips() -> None:
    devices = require_tpu(MESH_SHARDS)
    events, nows = trace(MESH_PORTS, PAPER.event_block)
    runs = {"(1,1)": (1, 1, "padded"), "(2,2) padded": (2, 2, "padded"),
            "(2,2) ragged": (2, 2, "ragged")}
    jobs = {}
    for name, (pods, shards, exchange) in runs.items():
        system = DFASystem(mesh_cfg(pods, shards, exchange),
                           make_dfa_mesh(pods, shards, devices=devices))
        per_device = MESH_PORTS * PAPER.event_block // system.n_shards
        lowered = system.jit_stream(donate=False, overlapped=False).lower(
            state_specs(system),
            event_structs(system, per_device, PERIODS),
            jax.ShapeDtypeStruct(nows.shape, nows.dtype,
                                 sharding=NamedSharding(
                                     system.mesh,
                                     jax.sharding.PartitionSpec())))
        jobs[name] = (system, lowered)
    # the three programs compile side by side on the host's cores
    t0 = time.perf_counter()
    with concurrent.futures.ThreadPoolExecutor(len(jobs)) as pool:
        futures = {name: pool.submit(lowered.compile)
                   for name, (_, lowered) in jobs.items()}
        compiled = {name: f.result() for name, f in futures.items()}
    log(f"compile_s (three mesh programs, in parallel): "
        f"{time.perf_counter() - t0:.3f}")

    results = {}
    for name, (system, _) in jobs.items():
        _, specs = system.event_specs(1, PERIODS)
        ev = {k: jax.device_put(v, NamedSharding(system.mesh, specs[k]))
              for k, v in events.items()}
        now = jax.device_put(nows, NamedSharding(
            system.mesh, jax.sharding.PartitionSpec()))
        out = compiled[name](system.init_sharded_state(), ev, now)
        jax.block_until_ready(out)
        placed = {s.device for s in out.state.collector.memory
                  .addressable_shards}
        log(f"{name}: ring shards on devices "
            f"{sorted(d.id for d in placed)}")
        check(len(placed) == system.n_shards,
              f"{name}: ring placed on {len(placed)} device(s), mesh has "
              f"{system.n_shards}")
        results[name] = (merged_state(system, out.state),
                         host_tree(out.metrics), system.wire)

    ref_state, ref_metrics, wire = results["(1,1)"]
    check(int(ref_metrics["reports_recv"].sum()) > 0, "no routed reports")
    check(int(ref_metrics["bucket_drops"].sum()) == 0, "bucket drops")
    check(bool((ref_state["rep.seq"] <= wire.seq_mask).all()),
          "a port wrapped its seq space; the invariance bound is void")
    for name in ("(2,2) padded", "(2,2) ragged"):
        got_state, got_metrics, _ = results[name]
        for k in ref_state:
            check(np.array_equal(ref_state[k], got_state[k]),
                  f"{name} vs (1,1): state {k} differs")
        for k in ref_metrics:
            check(np.array_equal(ref_metrics[k], got_metrics[k]),
                  f"{name} vs (1,1): metric {k} differs")
        log(f"{name} == (1,1): merged end state and {len(ref_metrics)} "
            f"per-period metrics bitwise over {PERIODS} periods")
    log(f"crosspod_sent per period (ragged): "
        f"{results['(2,2) ragged'][1]['crosspod_sent'].tolist()}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: serving path on one chip; 4: the (2,2) "
                    "mesh path only")
    args = ap.parse_args()
    (one_chip if args.chips == 1 else four_chips)()
    d = jax.devices()
    print(json.dumps({"ok": True, "device": {
        "platform": d[0].platform, "kind": d[0].device_kind,
        "count": len(d)}}), flush=True)


if __name__ == "__main__":
    main()
