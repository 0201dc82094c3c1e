"""The control of a cell's comparison: the plain reference put in the
program's place, with its features computed in the next precision down
(bfloat16, rounding after every operation, for the float32 the
configuration states), compared as a run compares the program.

    python3 benchmarks/chip/control.py --workload <cell> --seed <n> \\
        --periods <p>

``--periods`` is the number of periods a run of the cell serves. The
integer path (reporter, reports, collector) is the reference's own, so
only ``feature_gap`` can read differently from a sound run; it must come
out above its limit. Prints one JSON line: the cell, the seed, the
control's ``feature_gap`` (also over the inexact features alone) and
the reference's own float32 gap. Needs no
device.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import ml_dtypes
import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run  # noqa: E402
import traffic  # noqa: E402

def bf16(a):
    return np.asarray(a, np.float32).astype(ml_dtypes.bfloat16).astype(
        np.float32)


def finite_gap(got, want) -> float:
    """The gap over the features whose float32 bound is not 0 (those
    the formulas cannot compute exactly)."""
    keep = np.isfinite(want["enriched"]) & (want["bound"] > 0)
    d = np.abs(got.astype(np.float64) - want["exact"])
    return float(np.where(keep, d / np.where(keep, want["bound"], 1.0),
                          0.0).max(initial=0.0))


def control(workload: str, seed: int, periods: int, dfa_over=None,
            mix_over=None):
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    cell, conf = run.find_cell(bench, workload)
    cfg_file = run.load_json(run.ROOT, conf["file"])
    dfa = {**cfg_file["dfa"], **(dfa_over or {})}
    mix = {**traffic.load(cell["traffic"]), **(mix_over or {})}
    ref_mod = run.load_module(os.path.join(HERE, "configs",
                                           cfg_file["reference"]),
                              "reference_" + conf["name"])
    trace = traffic.build(mix, seed, dfa["event_block"])
    ref = ref_mod.Reference(dfa)
    replay = ref_mod.Replay(run.replay_events(trace), dfa["event_block"],
                            mix["events_per_period"],
                            dfa["monitoring_period_us"])
    sample = run.feature_periods(seed, periods)
    gap, finite, own = 0.0, 0.0, 0.0
    for t in range(periods):
        batch, now = replay.next_batch()
        out = ref.step(batch["ts"], batch["size"], batch["five_tuple"],
                       batch["valid"], now, enrich=t in sample, rnd=bf16)
        if t in sample:
            gap = max(gap, run.feature_gap(out["control"], out)[0])
            finite = max(finite, finite_gap(out["control"], out))
            own = max(own, run.feature_gap(out["enriched"], out)[0])
    return {"workload": workload, "seed": seed, "periods": periods,
            "control_feature_gap": gap,
            "control_feature_gap_inexact": finite,
            "reference_f32_feature_gap": own,
            "limit": cfg_file["limits"]["feature_gap"]}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--periods", type=int, required=True)
    a = ap.parse_args()
    print(json.dumps(control(a.workload, a.seed, a.periods)), flush=True)


if __name__ == "__main__":
    main()
