"""The comparison that decides ``correct``, shown to fail.

Runs on the CPU at a small size (the ``ref`` kernels, a 1,024-slot
table, 2,048-event batches): a sound run comes out correct; the control
(the reference in the program's place, features in bfloat16) and each
fault planted under the timed path come out not correct. Run by path:

    python -m pytest benchmarks/chip/tests/test_correct.py
"""
import argparse
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import pytest  # noqa: E402

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import control  # noqa: E402
import run  # noqa: E402

SMALL = {"flows_per_shard": 1024, "event_block": 2048,
         "report_capacity": 128, "flow_tile": 64, "kernel_backend": "ref"}
MIX = {"flows": 512, "trace_periods": 8}
PERIODS = 6
SEED = 2 ** 31 + 11


def keep_state(step):
    """The step returns the state it was given (a copy: the original is
    donated)."""
    def f(state, events, now):
        kept = jax.tree.map(jnp.copy, state)
        return step(state, events, now)._replace(state=kept)
    return f


def half_batch(step):
    """Half of every event batch is left out."""
    def f(state, events, now):
        n = events["valid"].shape[0]
        valid = events["valid"].at[n // 2:].set(False)
        return step(state, {**events, "valid": valid}, now)
    return f


def altered_feature(step):
    """One delivered feature is changed where it is produced."""
    def f(state, events, now):
        out = step(state, events, now)
        return out._replace(enriched=out.enriched.at[0, 1].add(1.0))
    return f


def altered_flow_id(step):
    """One delivered flow id is changed where it is produced."""
    def f(state, events, now):
        out = step(state, events, now)
        return out._replace(flow_ids=out.flow_ids.at[0].add(1))
    return f


def serve(workload, fault=None):
    args = argparse.Namespace(workload=workload, seed=SEED, seconds=1.0,
                              trace=0)
    hooks = run.Hooks(require_chip=False, dfa=SMALL, mix=MIX,
                      fault=fault, periods=PERIODS)
    return run.run(args, hooks)


@pytest.mark.parametrize("workload", ["port_busy", "port_offpeak"])
def test_sound_run_is_correct(workload):
    res = serve(workload)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("fault,check", [
    (keep_state, "state_words_off"),
    (half_batch, "state_words_off"),
    (altered_feature, "feature_gap"),
    (altered_flow_id, "outputs_off"),
])
def test_fault_is_not_correct(fault, check):
    res = serve("port_busy", fault)
    assert not res["correct"]
    c = res["checks"][check]
    assert c["value"] > c["limit"], res["checks"]


@pytest.mark.parametrize("workload", ["port_busy", "port_offpeak"])
def test_control_is_not_correct(workload):
    out = control.control(workload, SEED, PERIODS, SMALL, MIX)
    assert out["reference_f32_feature_gap"] <= out["limit"]
    assert out["control_feature_gap"] > out["limit"]
    assert out["control_feature_gap_inexact"] > out["limit"]
