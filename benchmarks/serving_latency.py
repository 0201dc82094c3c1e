"""Continuous serving under the SLO: per-period wall latency percentiles.

Runs the real serving loop (launch.serving) — trace-replay source,
double-buffered host ingest ring, donated per-period ``dfa_step`` — for
>= 100 periods and reports the wall-clock period latency distribution as
p50/p99/p999 rows. These are the rows the nightly ``compare_bench.py``
gate matches night over night: the paper's claim is an SLO (verdicts
inside the 20 ms monitoring period), so the regression signal must be a
latency percentile, not a throughput mean.

Two operating points:

* ``serving_latency_p50/p99/p999`` — offered rate == batch capacity
  (every period full, no queueing): the steady-state SLO numbers.
* ``serving_overrun_*`` derived rows — offered 2x capacity with a small
  host queue: exercises backpressure and checks the drop-accounting
  identity (``offered == processed + dropped`` after drain) inside the
  bench itself, so the nightly artifact records that the serving path
  sheds load exactly, never silently.

CPU wall numbers are relative only (no TPU in this container); the SLO
verdict column reports violations of the paper's 20 ms budget for
context, and the derived fields carry the events processed per second
of the loop's wall time.

Standalone: ``python benchmarks/serving_latency.py --tiny --json out.json``
(also wired into benchmarks/run.py for the CI bench-smoke artifact).
"""
from __future__ import annotations

import os
import sys

if __package__ in (None, ""):           # executed as a script: mirror
    _root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(_root, "src"))   # run.py's sys.path
    sys.path.insert(0, _root)
    if "--tiny" in sys.argv:            # before benchmarks.common binds TINY
        os.environ["REPRO_BENCH_TINY"] = "1"

import dataclasses
import time

from benchmarks.common import TINY, csv
from repro.compat import make_mesh
from repro.configs import get_dfa_config
from repro.core.pipeline import DFASystem
from repro.data import packets as PK
from repro.launch.serving import ServingLoop, build_source

PERIODS = 100 if TINY else 256


def run():
    mesh = make_mesh((1, 1), ("data", "model"))
    base = get_dfa_config(reduced=True)
    E = base.event_block
    budget_us = base.monitoring_period_us
    capacity_eps = E / (budget_us / 1e6)    # one full batch per period
    trace_T = 4
    events, nows = PK.period_batches(1, trace_T, E, n_flows=32,
                                     flow_seed=0)

    # -- steady state: offered == capacity, no queue, no drops ----------
    cfg = dataclasses.replace(base, serve_offered_eps=capacity_eps)
    system = DFASystem(cfg, mesh)
    # warm-up loop on its own source: the measured run then serves every
    # period through the already-compiled step (jit_step is cached on the
    # system), so p999 reflects serving jitter, not the one-off compile
    ServingLoop(system, build_source(system, events, nows)).run(3)
    t0 = time.perf_counter()
    report = ServingLoop(system, build_source(system, events, nows)).run(
        PERIODS)
    wall_s = time.perf_counter() - t0
    assert report.balanced, "serving accounting must close"
    assert report.dropped == 0, "steady state must not shed load"
    lat = report.latency
    ctx = (f"periods={PERIODS};budget_us={budget_us};"
           f"offered_eps={capacity_eps:.3e};"
           f"processed_eps={report.processed / wall_s:.3e};"
           f"violations={report.violations}")
    csv("serving_latency_p50", lat["p50"], ctx)
    csv("serving_latency_p99", lat["p99"], ctx)
    csv("serving_latency_p999", lat["p999"], ctx)

    # -- forced overrun: 2x capacity, bounded queue, exact shedding -----
    cfg_o = dataclasses.replace(base,
                                serve_offered_eps=2.0 * capacity_eps,
                                serve_queue_events=2 * E,
                                drop_policy="newest")
    sys_o = DFASystem(cfg_o, mesh)
    t0 = time.perf_counter()
    rep_o = ServingLoop(sys_o, build_source(sys_o, events, nows)).run(
        PERIODS)
    wall_o = time.perf_counter() - t0
    assert rep_o.balanced, \
        (rep_o.offered, rep_o.processed, rep_o.dropped)
    assert rep_o.dropped > 0, "2x offered must force drops"
    lat_o = rep_o.latency
    csv("serving_overrun_p99", lat_o["p99"],
        f"periods={PERIODS};drained={rep_o.drained_periods};"
        f"offered_eps={2.0 * capacity_eps:.3e};"
        f"processed_eps={rep_o.processed / wall_o:.3e}")
    csv("serving_overrun_accounting", 0.0,
        f"offered={rep_o.offered};processed={rep_o.processed};"
        f"dropped={rep_o.dropped};exact="
        f"{rep_o.offered == rep_o.processed + rep_o.dropped};"
        f"drop_policy=newest;queue_events={2 * E}")


def _main():
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--tiny", action="store_true",
                    help="bench-smoke mode (already applied pre-import)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write rows as a JSON artifact")
    args = ap.parse_args()
    from benchmarks import common
    print("name,us_per_call,derived")
    run()
    if args.json:
        common.write_artifact(args.json, tag="serving_latency")


if __name__ == "__main__":
    _main()
