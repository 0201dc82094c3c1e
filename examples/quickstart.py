"""Quickstart: the paper's loop in 60 lines — packets in, per-flow Table-I
features extracted at the reporter, DTA-routed to collector shards, placed
in the Fig-4 ring buffer, enriched, ready for inference.

    PYTHONPATH=src python examples/quickstart.py
"""
import sys

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp
import numpy as np

from repro.compat import make_mesh
from repro.configs import get_dfa_config
from repro.core.pipeline import DFASystem
from repro.data import packets as PK
from repro.launch.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    mesh = make_mesh((1, 1), ("data", "model"))
    cfg = get_dfa_config(reduced=True)
    system = DFASystem(cfg, mesh)
    state = system.init_state()
    step = jax.jit(system.dfa_step, donate_argnums=(0,))

    flows = PK.gen_flows(32, seed=0)
    print(f"monitoring {len(flows['rate'])} flows, "
          f"period={cfg.monitoring_period_us/1000:.0f} ms, "
          f"history={cfg.history} entries/flow")
    with mesh:
        for period in range(3):
            ev = PK.events_for_shards(flows, period, system.n_shards, 512,
                                      window_us=cfg.monitoring_period_us)
            now = jnp.uint32((period + 1) * cfg.monitoring_period_us * 2)
            out = step(
                state, {k: jnp.asarray(v) for k, v in ev.items()}, now)
            state, metrics = out.state, out.metrics
            got = int(np.asarray(out.mask).sum())
            en = np.asarray(out.enriched)[np.asarray(out.mask)]
            print(f"period {period}: {int(metrics['reports_sent'])} reports"
                  f" -> {got} feature vectors "
                  f"(mean pkts/flow {en[:, 0].mean():.1f}, "
                  f"mean rate {en[:, 12].mean()/1e6:.2f} Mb/s, "
                  f"checksum errors {int(metrics['bad_checksum'])})")
    ring = np.asarray(state.collector.entry_valid).sum()
    print(f"collector ring entries written: {ring} "
          f"(64 B each, verbatim RoCEv2 payloads)")


if __name__ == "__main__":
    main()
