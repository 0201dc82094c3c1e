"""What the program tells a profiler: the step's stage scopes, the
serving loop's ``serve/*`` spans, and the ``reports_due`` counter.

* every stage of the step is a ``jax.named_scope`` inside the
  ``shard_map`` bodies, so each device op's ``op_name`` metadata names
  the stage that owns it (the 1D path, the (pod, shard) mesh path, and
  the 1D path with the fault injector and a verdict head armed);
* ``ServingLoop.run`` under ``jax.profiler`` writes one span per loop
  phase, in loop order, each with the ``period`` it serves;
* ``metrics["reports_due"]`` counts every due flow before the report
  capacity cuts them, in both ingest variants.
"""
import dataclasses
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import pod_mesh_or_skip
from repro.compat import make_mesh
from repro.configs.dfa import REDUCED
from repro.core.pipeline import DFASystem
from repro.data import packets as PK
from repro.data import scenarios as SC
from repro.data.faults import FaultSpec
from repro.launch.serving import ServingLoop, build_source

# stage -> its child scopes
STAGES = {"reporter": ("ingest", "due", "reports"), "route": (),
          "exchange": (), "translate": (), "faults": (),
          "collector": ("validate", "place"), "enrich": ("infer",)}
WRAPPED = re.compile(r"^[\w-]+\((.*)\)$")     # vmap(due) -> due


def stage_path(op_name: str):
    """``jit(dfa_step)/.../collector/validate/sort`` -> the chain of known
    stage scopes in it (``collector/validate``), or None."""
    chain = []
    for part in op_name.split("/"):
        m = WRAPPED.match(part)
        while m:
            part = m.group(1)
            m = WRAPPED.match(part)
        if not chain and part in STAGES:
            chain = [part]
        elif chain and len(chain) == 1 and part in STAGES[chain[0]]:
            chain.append(part)
    return "/".join(chain) or None


def _mesh2d_cfg(port_report_capacity=8):
    return dataclasses.replace(
        REDUCED, flow_home="hash", pods=1, ports_per_pod=2,
        reporter_slots=64, flows_per_shard=256,
        port_report_capacity=port_report_capacity, kernel_backend="ref")


def _system(path):
    if path == "mesh2d":
        return DFASystem(_mesh2d_cfg(), pod_mesh_or_skip(1, 2))
    mesh = make_mesh((1, 1), ("data", "model"))
    cfg = dataclasses.replace(REDUCED, kernel_backend="ref")
    if path == "1x1_armed":
        cfg = dataclasses.replace(
            cfg, inference_head="linear",
            fault_spec=FaultSpec(seed=7, drop_rate=0.1, dup_rate=0.1))
    return DFASystem(cfg, mesh)


def _zero_events(system):
    sds, _ = system.event_specs(system.cfg.event_block)
    return {k: jnp.zeros(v.shape, v.dtype) for k, v in sds.items()}


@pytest.mark.parametrize("path,extra", [
    ("1x1", ()),
    ("mesh2d", ("exchange",)),
    ("1x1_armed", ("faults", "enrich/infer")),
])
def test_step_ops_carry_stage_scopes(path, extra):
    system = _system(path)
    lowered = system.jit_step(donate=False).lower(
        system.init_sharded_state(), _zero_events(system), jnp.uint32(0))
    names = re.findall(r'loc\("([^"]+)"', lowered.as_text(debug_info=True))
    found = {stage_path(n) for n in names} - {None}
    want = {"reporter/ingest", "reporter/due", "reporter/reports", "route",
            "translate", "collector", "collector/validate",
            "collector/place", "enrich", *extra}
    assert want <= found, sorted(want - found)
    if "exchange" not in extra:
        assert "exchange" not in found
    if "faults" not in extra:
        assert not {"faults", "enrich/infer"} & found


def _serve_spans(log_dir):
    (path,) = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    spans = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("serve/"):
                    spans.append((e.start_ns, e.name, dict(e.stats)))
    return [(name, stats.get("period")) for _, name, stats in sorted(spans)]


def test_serving_loop_writes_its_phases_with_their_period(tmp_path):
    mesh = make_mesh((1, 1), ("data", "model"))
    E = REDUCED.event_block
    cfg = dataclasses.replace(REDUCED, kernel_backend="ref",
                              serve_offered_eps=E / 0.02)
    system = DFASystem(cfg, mesh)
    events, nows = PK.period_batches(1, 3, E, n_flows=16, flow_seed=1)
    ServingLoop(system, build_source(system, events, nows)).run(1)  # warm
    loop = ServingLoop(system, build_source(system, events, nows))
    with jax.profiler.trace(str(tmp_path)):
        report = loop.run(3)
    assert report.drained_periods == 0
    want = [("serve/next_batch", 0), ("serve/stage", 0)]
    for k in range(3):
        want.append(("serve/dispatch", k))
        if k < 2:
            want += [("serve/next_batch", k + 1), ("serve/stage", k + 1)]
        want.append(("serve/wait", k))
    assert _serve_spans(tmp_path) == want


def _due_numpy(prev_state, state, now, period_us):
    """Flows due at ``now``: admitted after this period's ingest, and
    ``now - last_report`` (u32) of at least one period before its
    reports."""
    elapsed = (np.uint32(now) - np.asarray(prev_state.reporter.last_report)
               ).astype(np.uint32)
    active = np.asarray(state.reporter.active)
    return int((active & (elapsed >= np.uint32(period_us))).sum())


@pytest.mark.parametrize("path", ["1x1", "mesh2d"])
def test_reports_due_counts_flows_past_the_capacity(path):
    if path == "mesh2d":
        system = DFASystem(_mesh2d_cfg(port_report_capacity=4),
                           pod_mesh_or_skip(1, 2))
        ev, _ = SC.build("cross_pod_mix", system.total_ports, 64, 2,
                         seed=3)
        capacity = system.total_ports * system.port_capacity
    else:
        cfg = dataclasses.replace(REDUCED, kernel_backend="ref",
                                  report_capacity=16)
        system = DFASystem(cfg, make_mesh((1, 1), ("data", "model")))
        ev, _ = PK.period_batches(1, 2, cfg.event_block, n_flows=64,
                                  flow_seed=2)
        capacity = cfg.report_capacity
    period_us = system.cfg.monitoring_period_us
    # the second period comes half a period later: the flows reported in
    # the first are not due again, the ones the capacity deferred are
    nows = [period_us, period_us + period_us // 2]
    step = jax.jit(system.dfa_step)
    state = system.init_sharded_state()
    for t, now in enumerate(nows):
        out = step(state, {k: jnp.asarray(v[t]) for k, v in ev.items()},
                   jnp.uint32(now))
        due = int(out.metrics["reports_due"])
        sent = int(out.metrics["reports_sent"])
        assert due == _due_numpy(state, out.state, now, period_us), t
        assert sent <= due, t
        if t == 0:
            assert due > capacity
            assert sent == capacity
        state = out.state
