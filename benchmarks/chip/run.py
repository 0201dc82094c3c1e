"""The DFA serving path on the chip: one cell of ``BENCHMARK.json``.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

A cell is a configuration (``configs/<name>.json``, with its plain
reference beside it) under a traffic mix (``traffic/<name>.json``); a
per-layer metric is a reader ``layers/<name>.py``. All are found by the
names in ``BENCHMARK.json``, so a cell, configuration or metric is added
by adding files and entries.

One run:

1. set-up: the compile cache, the system from the configuration, the
   cell's event stream from ``--seed``, one step of the cell's own shape
   (compiles or loads it) and a few calibration periods that fix how
   many periods fill ``--seconds``;
2. the window: ``ServingLoop`` serves those periods back to back from a
   fresh state — ``TraceReplaySource`` → ``HostIngestRing`` → the
   donated ``DFASystem.jit_step`` — with the benchmark's host clock around
   each batch and dispatch, and with ``--trace 1`` the profiler on;
3. after the window: the device's memory peak, then the plain reference
   over the same events, compared with every period's reports, counters
   and flow ids, a seeded sample of periods' features, the end state and
   the accounting.

The last stdout line is the JSON result; the numbers compared, each with
its limit, close standard error and the result's line. Without a TPU, or
on a device missing from ``peaks.json``, the run exits non-zero and
prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Callable, Dict, List, Optional  # noqa: E402

import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))

import traffic  # noqa: E402
import trace_reduce  # noqa: E402

CALIBRATION_PERIODS = 3
MIN_PERIODS = 8
FEATURE_SAMPLE = 8          # periods whose features are compared, + last
M32 = 0xFFFFFFFF


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(*parts) -> Dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Hooks:
    """What a test changes in a run: off the chip, smaller sizes, a
    fault planted in the timed path (``fault(step) -> step``)."""

    def __init__(self, require_chip: bool = True, dfa: Optional[Dict] = None,
                 mix: Optional[Dict] = None,
                 fault: Optional[Callable] = None,
                 periods: Optional[int] = None):
        self.require_chip = require_chip
        self.dfa = dfa or {}
        self.mix = mix or {}
        self.fault = fault
        self.periods = periods


def find_cell(bench: Dict, name: str):
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: "
                         f"{sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    return cell, configs[cell["config"]]


def require_devices(jax, chips: int, peaks: Dict, hooks: Hooks):
    devices = jax.devices()
    if hooks.require_chip:
        if devices[0].platform != "tpu":
            raise SystemExit(f"no TPU: JAX found {devices[0].platform!r}")
        if len(devices) < chips:
            raise SystemExit(f"the cell needs {chips} chips, JAX found "
                             f"{len(devices)}")
        if devices[0].device_kind not in peaks["devices"]:
            raise SystemExit(f"device {devices[0].device_kind!r} is not in "
                             f"peaks.json")
    return devices[:chips]


# -- the timed path's instruments ---------------------------------------------

class Recorder:
    """Host clock around the benchmark's calls into the program, and the
    period outputs kept on the device for the comparison."""

    def __init__(self, annotate: bool, keep_features: set):
        self.annotate = annotate
        self.keep_features = keep_features
        self.build_start: List[float] = []
        self.build_s: List[float] = []
        self.stage_s: List[float] = []
        self.dispatch_t: List[float] = []
        self.outputs: List[Dict] = []
        self.features: Dict[int, object] = {}

    def span(self, name: str):
        if not self.annotate:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def on_step(self, t: float, out) -> None:
        k = len(self.dispatch_t)
        self.dispatch_t.append(t)
        self.outputs.append({"flow_ids": out.flow_ids, "mask": out.mask,
                             "metrics": out.metrics})
        if k in self.keep_features:
            self.features[k] = out.enriched


class TimedSource:
    """The replay source, with the host clock around ``next_batch``."""

    def __init__(self, inner, rec: Recorder):
        self._inner, self._rec = inner, rec

    def next_batch(self):
        t0 = time.perf_counter()
        with self._rec.span("next_batch"):
            out = self._inner.next_batch()
        self._rec.build_start.append(t0)
        self._rec.build_s.append(time.perf_counter() - t0)
        return out

    def __getattr__(self, name):
        return getattr(self._inner, name)


class TimedSystem:
    """The system, with the host clock around each step's dispatch."""

    def __init__(self, inner, rec: Recorder, fault=None):
        self._inner, self._rec, self._fault = inner, rec, fault

    def jit_step(self, donate: bool = True):
        step = self._inner.jit_step(donate)
        if self._fault is not None:
            step = self._fault(step)
        rec = self._rec

        def timed(state, events, now):
            t = time.perf_counter()
            with rec.span("dispatch"):
                out = step(state, events, now)
            rec.on_step(t, out)
            return out
        return timed

    def __getattr__(self, name):
        return getattr(self._inner, name)


def timed_stage(ring, rec: Recorder) -> None:
    stage = ring.stage

    def timed(batch, now):
        t0 = time.perf_counter()
        with rec.span("stage"):
            out = stage(batch, now)
        rec.stage_s.append(time.perf_counter() - t0)
        return out
    ring.stage = timed


def replay_events(trace: Dict) -> Dict[str, np.ndarray]:
    """The cell's stream as the stacked trace ``TraceReplaySource``
    takes."""
    size = trace["size"].reshape(1, -1)
    return {"ts": np.zeros(size.shape, np.uint32), "size": size,
            "five_tuple": trace["five_tuple"].reshape(1, -1, 5),
            "valid": np.ones(size.shape, bool)}


# -- the comparison -------------------------------------------------------------

def host_state(state) -> Dict[str, np.ndarray]:
    import jax
    out = {}
    for part in ("reporter", "translator", "collector"):
        for k, v in getattr(state, part)._asdict().items():
            out[f"{part}.{k}"] = np.asarray(jax.device_get(v))
    return out


def feature_periods(seed: int, n_periods: int) -> set:
    """The periods whose features are compared: drawn from the seed,
    the last always among them."""
    rng = np.random.default_rng([seed, 1])
    return set(rng.choice(n_periods, min(FEATURE_SAMPLE, n_periods),
                          replace=False).tolist()) | {n_periods - 1}


def words_off(got: np.ndarray, want: np.ndarray) -> int:
    got = np.asarray(got).reshape(-1)
    want = np.asarray(want).reshape(-1)
    if got.shape != want.shape:
        return max(got.size, want.size)
    if got.dtype == bool or want.dtype == bool:
        return int((got.astype(bool) != want.astype(bool)).sum())
    return int(((got.astype(np.uint64) & M32)
                != (want.astype(np.uint64) & M32)).sum())


def feature_gap(got: np.ndarray, want: Dict) -> float:
    """Widest distance of a delivered feature from the reference's
    float64 value, in units of that feature's float32 rounding-error
    bound (``want["bound"]``): at most 1 for any float32 evaluation of
    the formulas. A feature the float32 reference makes non-finite must
    be the same non-finite value."""
    ref32, exact, bound = want["enriched"], want["exact"], want["bound"]
    fin = np.isfinite(ref32)
    if not (np.array_equal(fin, np.isfinite(got))
            and np.array_equal(got[~fin], ref32[~fin])):
        return float("inf"), []
    d = np.abs(got.astype(np.float64) - exact)
    with np.errstate(divide="ignore", invalid="ignore"):
        ratio = np.where(fin & (d > 0), d / bound, 0.0)
    ratio = np.where(np.isnan(ratio), np.inf, ratio)
    rows = ratio.argmax(axis=0)                 # worst row per column
    cols = np.argsort(ratio[rows, np.arange(ratio.shape[1])])[::-1][:3]
    return float(ratio.max(initial=0.0)), [
        [int(r), int(c), float(ratio[r, c]), float(got[r, c]),
         float(exact[r, c]), float(bound[r, c])]
        for r, c in zip(rows[cols], cols)]


def compare(ref_mod, dfa: Dict, trace: Dict, budget_us: int,
            offered_per_period: int, batch_events: int, n_periods: int,
            outputs: List[Dict], features: Dict[int, np.ndarray],
            state: Dict[str, np.ndarray], report):
    """Replay the window through the reference; the compared numbers and
    the reference's per-period work counts."""
    ref = ref_mod.Reference(dfa)
    replay = ref_mod.Replay(replay_events(trace), batch_events,
                            offered_per_period, budget_us)
    outputs_off, gap, worst = 0, 0.0, []
    for t in range(n_periods):
        batch, now = replay.next_batch()
        want = ref.step(batch["ts"], batch["size"], batch["five_tuple"],
                        batch["valid"], now, enrich=t in features)
        got = outputs[t]
        outputs_off += words_off(got["flow_ids"], want["flow_ids"])
        outputs_off += words_off(got["mask"], want["mask"])
        for k, v in want["metrics"].items():
            outputs_off += words_off(got["metrics"][k], np.asarray(v))
        if t in features:
            g, w = feature_gap(features[t], want)
            if not g <= gap:
                gap, worst = g, [[t] + x for x in w]
    rstate = ref.state()
    state_off = sum(words_off(state[k], v) for k, v in rstate.items())
    state_off += sum(np.asarray(v).size for k, v in state.items()
                     if k not in rstate)
    acct = (abs(report.offered - report.processed - report.dropped)
            + abs(report.offered - replay.offered)
            + abs(report.processed - replay.processed)
            + abs(report.dropped - replay.dropped))
    checks = {"state_words_off": state_off, "outputs_off": outputs_off,
              "accounting_off": acct, "feature_gap": gap}
    counters = {k: int(rstate[f"collector.{k}"][0]) for k in (
        "received", "seq_anomalies", "lost_reports", "bad_checksum")}
    return checks, ref.work, counters, worst


# -- one run ----------------------------------------------------------------------

def run(args, hooks: Optional[Hooks] = None) -> Dict:
    hooks = hooks or Hooks()
    bench = load_json(ROOT, "BENCHMARK.json")
    cell, conf = find_cell(bench, args.workload)
    cfg_file = load_json(ROOT, conf["file"])
    peaks = load_json(HERE, "peaks.json")

    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, secs, **kw: compiles.append(secs)
        if ev == "/jax/core/compile/backend_compile_duration" else None)
    devices = require_devices(jax, cell["chips"], peaks, hooks)
    kind = devices[0].device_kind
    peak = peaks["devices"].get(kind)

    import dataclasses
    from repro.compat import make_mesh
    from repro.configs.base import DFAConfig
    from repro.core.pipeline import DFASystem
    from repro.launch.serving import ServingLoop, build_source

    dfa = {**cfg_file["dfa"], **hooks.dfa}
    mix = {**traffic.load(cell["traffic"]), **hooks.mix}
    budget = dfa["monitoring_period_us"]
    per_period = mix["events_per_period"]
    dfa["serve_offered_eps"] = per_period * 1e6 / budget
    cfg = dataclasses.replace(DFAConfig(), **dfa)
    mesh = make_mesh(tuple(cfg_file["mesh"]), ("data", "model"),
                     devices=devices)
    system = DFASystem(cfg, mesh)
    desc = system.describe()
    log("describe: " + json.dumps(desc, default=str))
    log(f"compile cache: {cache_dir}")
    if system.total_ports != 1:
        raise SystemExit(f"the traffic feeds one port, the system has "
                         f"{system.total_ports}")
    batch_events = system.n_shards * cfg.event_block

    trace = traffic.build(mix, args.seed, cfg.event_block)
    events = replay_events(trace)

    # set-up: one step of the cell's shape, then calibration periods
    rec0 = Recorder(False, set())
    warm_loop = ServingLoop(TimedSystem(system, rec0),
                            TimedSource(build_source(system, events), rec0))
    batch, now, _ = build_source(system, events).next_batch()
    warm = system.jit_step(donate=True)(system.init_sharded_state(),
                                        *warm_loop.ring.stage(batch, now))
    jax.block_until_ready(warm)
    del warm
    cal = warm_loop.run(CALIBRATION_PERIODS,
                        state=system.init_sharded_state())
    # a loop iteration in steady state: between the first and the last
    # calibration period's outputs
    done = [t + lat / 1e6 for t, lat in zip(rec0.dispatch_t,
                                            cal.latency_us)]
    per_s = (done[-1] - done[0]) / (len(done) - 1)
    del cal, warm_loop
    n_periods = hooks.periods or max(MIN_PERIODS,
                                     int(round(args.seconds / per_s)))
    sample = feature_periods(args.seed, n_periods)

    rec = Recorder(bool(args.trace), sample)
    loop = ServingLoop(TimedSystem(system, rec, hooks.fault),
                       TimedSource(build_source(system, events), rec))
    timed_stage(loop.ring, rec)
    state = system.init_sharded_state()
    jax.block_until_ready(state)
    gc.collect()
    n_compiles = len(compiles)
    trace_dir = tempfile.mkdtemp(prefix="dfa_trace_") if args.trace else None
    if trace_dir:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    with rec.span("window"):
        report = loop.run(n_periods, state=state)
    t_end = time.perf_counter()
    if trace_dir:
        jax.profiler.stop_trace()
    window_s = t_end - rec.build_start[0]
    setup_s = rec.build_start[0] - T_START
    window_compiles = len(compiles) - n_compiles
    done = [t + lat / 1e6 for t, lat in zip(rec.dispatch_t,
                                            report.latency_us)]
    period_s = [d - b for d, b in zip(done, rec.build_start)]
    memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for d in devices)

    # everything the comparison needs, to the host; then free the device
    outputs = jax.device_get(rec.outputs)
    features = {k: np.asarray(v) for k, v in
                jax.device_get(rec.features).items()}
    state_h = host_state(report.last.state)
    fv = int(sum(np.asarray(o["mask"]).sum() for o in outputs))
    # delivered rows whose payload the collector rejected as a seq
    # duplicate: enriched from the ring rows it already held
    seq_anomalies = int(state_h["collector.seq_anomalies"].reshape(-1)[0])
    acct = {k: getattr(report, k) for k in ("offered", "processed",
                                             "dropped")}
    del loop, state, rec.outputs, rec.features
    report_last, report.last = report.last, None
    del report_last
    gc.collect()
    log(f"window: {n_periods} periods in {window_s:.6f} s "
        f"(calibration {per_s:.6f} s a period), compiles in window "
        f"{window_compiles}, accounting {json.dumps(acct)}, "
        f"feature vectors {fv} ({seq_anomalies} of them over payloads "
        f"rejected as seq duplicates), memory_peak_bytes {memory_peak}")

    ref_mod = load_module(os.path.join(HERE, "configs",
                                       cfg_file["reference"]),
                          "reference_" + conf["name"])
    t0 = time.perf_counter()
    checks, work, counters, worst = compare(
        ref_mod, dfa, trace, budget, per_period, batch_events, n_periods,
        outputs, features, state_h, report)
    log(f"reference: {time.perf_counter() - t0:.3f} s; collector "
        f"counters {json.dumps(counters)}; widest feature gaps "
        f"[period, row, column, gap, got, float64, bound]: {worst}")
    limits = cfg_file["limits"]
    checks = {k: {"value": v, "limit": limits[k]} for k, v in checks.items()}
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": int(memory_peak)}
    result = {"correct": correct, "attempted": report.offered,
              "failed": report.dropped}
    if args.trace:
        raw = trace_reduce.read_xplane(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        red = trace_reduce.reduce(raw)
        ctx = {"trace": red, "periods": n_periods, "work": work,
               "host_s": [b + s for b, s in zip(rec.build_s, rec.stage_s)],
               "peaks": peak, "dfa": dfa, "fv": fv,
               "seq_anomalies": seq_anomalies}
        metrics = {}
        for m in bench["per_layer"]:
            reader = load_module(os.path.join(HERE, "layers",
                                              m["name"] + ".py"),
                                 "layer_" + m["name"])
            v = reader.read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if red is not None:
            device.update(busy_s=red["busy_ns"] / 1e9,
                          window_s=red["window_ns"] / 1e9)
            result["breakdown"] = {"device_ops": red["device_ops"],
                                   "idle_gaps": red["idle_gaps"]}
    else:
        values = {
            "events_per_s": ("events/s", report.processed / window_s),
            "fv_per_s": ("fv/s", fv / window_s),
            "period_p90_ms": ("ms", float(np.percentile(period_s, 90))
                              * 1e3),
            "setup_s": ("s", setup_s),
        }
        metrics = {m["name"]: {"value": values[m["name"]][1],
                               "unit": values[m["name"]][0]}
                   for m in bench["end_to_end"]}
    result.update(metrics=metrics, device=device, checks=checks)
    for k, c in checks.items():
        log(f"check {k}: {c['value']!r} (limit {c['limit']!r})")
    return result


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    result = run(ap.parse_args(argv))
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
