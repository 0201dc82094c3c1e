"""End-to-end ONLINE serving (the paper's headline use case): a continuous
period loop under a latency SLO — packets replayed at a configured offered
rate, host-staged through the double-buffered ingest ring (period t+1's
events upload while period t computes), per-flow verdicts from the
streaming inference hook every period, per-period wall latency measured
against the 20 ms budget with exact drop accounting. A small LM backbone
then consumes the most suspicious flows of the final period as a second,
heavier stage.

    PYTHONPATH=src python examples/serve_traffic_inference.py

Pipeline: trace-replay source (paced events/s)
            -> HostIngestRing (double-buffered jax.device_put)
            -> donated dfa_step per period: ingest -> enrich
               -> per-flow verdict logits (models.registry flow head)
            -> ServingReport: p50/p99/p999 period latency, SLO
               violations, offered == processed + dropped
            -> the top flows' verdict classes become the prompt tokens
               for the granite-3-2b (reduced) backbone
               -> batched prefill+decode.
"""
import sys

sys.path.insert(0, "src")

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.compat import make_mesh
from repro.configs import get_config, get_dfa_config
from repro.core.pipeline import DFASystem
from repro.data import packets as PK
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.serve import serve
from repro.launch.serving import ServingLoop, build_source
from repro.models.registry import get_model


def main():
    enable_compile_cache()
    mesh = make_mesh((1, 1), ("data", "model"))
    # arm the streaming inference hook + the serving knobs: offer events
    # 25% above the batch-capacity rate so backpressure (queueing + tail
    # drop) is actually exercised, not just configured
    dfa_cfg = dataclasses.replace(get_dfa_config(reduced=True),
                                  inference_head="linear",
                                  inference_classes=8)
    capacity_eps = (dfa_cfg.event_block
                    / (dfa_cfg.monitoring_period_us / 1e6))
    dfa_cfg = dataclasses.replace(dfa_cfg,
                                  serve_offered_eps=1.25 * capacity_eps,
                                  serve_queue_events=2 * dfa_cfg.event_block,
                                  drop_policy="newest")
    system = DFASystem(dfa_cfg, mesh)
    periods = 16
    events, nows = PK.period_batches(system.n_shards, 4,
                                     dfa_cfg.event_block, n_flows=24,
                                     flow_seed=3)

    cfg = get_config("granite-3-2b", reduced=True)
    model = get_model(cfg, mesh)
    params = model.init(jax.random.key(0))

    t0 = time.time()
    with mesh:
        loop = ServingLoop(system, build_source(system, events, nows))
        t_loop = time.time()
        report = loop.run(periods)          # drains the queue on shutdown
        loop_s = time.time() - t_loop
        out = report.last                    # StepOutputs, final period
        em = np.asarray(out.mask)
        verdicts = np.asarray(jnp.argmax(out.preds, axis=-1))
        scores = np.asarray(jax.nn.logsumexp(out.preds, axis=-1))
        # stage 2: the 4 highest-scoring flows of the final period go to
        # the LM backbone; each flow's prompt is its verdict class id
        # (offset past token 0) — a flow-dependent prefix, so different
        # telemetry produces different stage-2 inputs
        rows = np.nonzero(em)[0]
        rows = rows[np.argsort(-scores[rows])][:4]
        B = max(1, len(rows))
        vcls = (verdicts[rows] if len(rows) else np.zeros(1, np.int64))
        vtok = jnp.asarray(vcls.reshape(B, 1) + 1, jnp.int32)
        prompt = {"tokens": jnp.concatenate(
            [jnp.zeros((B, 4), jnp.int32),
             jnp.tile(vtok, (1, 4))], axis=1)}
        toks, tps = serve(model, params, prompt, 8, 8, 32)
    dt = time.time() - t0

    lat = report.latency
    assert report.balanced, "accounting must close after drain"
    print(f"{report.periods} serving periods (+{report.drained_periods} "
          f"drain), SLO budget {report.budget_us / 1000:.0f} ms")
    print(f"offered {report.offered} == processed {report.processed} "
          f"+ dropped {report.dropped} (exact, drop_policy="
          f"{system.cfg.drop_policy})")
    print(f"period latency: p50 {lat['p50'] / 1000:.1f} ms, "
          f"p99 {lat['p99'] / 1000:.1f} ms, "
          f"p999 {lat['p999'] / 1000:.1f} ms; "
          f"{report.violations} budget violations "
          f"(CPU container — TPU is the SLO target)")
    print(f"processed {report.processed / loop_s:.3e} events/s of wall "
          f"time ({system.cfg.serve_offered_eps:.3e} offered, first "
          f"period's compile included)")
    v, c = np.unique(verdicts[em], return_counts=True)
    print(f"final period: {int(em.sum())} flows enriched, verdict "
          f"histogram {dict(zip(v.tolist(), c.tolist()))}")
    print(f"stage-2 batch: {B} flows {np.asarray(out.flow_ids)[rows]}")
    print(f"verdict tokens per flow: {np.asarray(toks)[:, :6]}")
    print(f"end-to-end (serve loop + verdicts -> tokens) {dt*1000:.0f} ms; "
          f"decode {tps:.1f} tok/s; paper target: sub-20 ms periods")


if __name__ == "__main__":
    main()
