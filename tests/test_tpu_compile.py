"""Compile the main-path Pallas kernels, and the whole paper-scale step,
for a TPU v5e chip that is described, not attached.

The TPU compiler ships with jaxlib, so these compiles run on a CPU-only
host and catch what interpret mode cannot: casts and ops Mosaic does not
lower, block shapes and DMA slices that do not match the memory tiling,
kernels that overrun VMEM, and a step that does not fit the chip's 16 GB
of HBM. A compile that passes is not a chip run; ``chip_smoke.py`` is.

The topology is described inside a module-scoped fixture (never while a
module is imported), so every pytest-xdist worker collects the same tests
and only the worker given this file loads the TPU library. The persistent
compilation cache stays off around the compiles: an entry written for a
described chip cannot be read back without one.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.configs.dfa import PAPER
from repro.core.pipeline import DFASystem
from repro.kernels import dispatch
from repro.kernels.gather_enrich import kernel as GE
from repro.kernels.ingest_update import kernel as IU
from repro.kernels.ring_scatter import kernel as RS

HBM_BYTES = 16 * 10**9            # one v5e chip
F, H, R = PAPER.flows_per_shard, PAPER.history, PAPER.report_capacity
BITS, ET, D = PAPER.logstar_bits, PAPER.event_tile, PAPER.derived_dim


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _kernels(compiled):
    return dispatch.tpu_kernels(compiled.as_text())


def _ingest_block(s):
    E = 1024
    return (lambda *a: IU.segment_sums_pallas(
        *a, bits=BITS, event_tile=ET, interpret=False),
        [s((E,), jnp.int32)] + [s((E,), jnp.uint32)] * 3
        + [s((E,), jnp.int32)])


def _ingest_hbm(s):
    E = 1 << 20
    return (lambda *a: IU.segment_sums_hbm_pallas(
        *a, bits=BITS, event_tile=ET, interpret=False),
        [s((E // ET,), jnp.int32), s((E,), jnp.int32)]
        + [s((E,), jnp.uint32)] * 3 + [s((E,), jnp.int32)])


def _ring_scatter(s, R=R):
    return (lambda m, p, f, h, k: RS.ring_scatter_pallas(
        m, p, f, h, k, flow_tile=PAPER.flow_tile, history=H,
        interpret=False),
        [s((F, H, 16), jnp.uint32), s((R, 16), jnp.uint32),
         s((R,), jnp.int32), s((R,), jnp.int32), s((R,), jnp.bool_)])


def _ring_scatter_2r(s):
    """Twice the PAPER reports: VMEM holds the ring tile and two payload
    chunks, so it no longer bounds R."""
    return _ring_scatter(s, R=2 * R)


def _gather_hbm(s):
    return (lambda m, v, f: GE.gather_enrich_hbm_pallas(
        m, v, f, derived_dim=D, report_tile=PAPER.flow_tile,
        interpret=False),
        [s((F, H, 16), jnp.uint32), s((F, H), jnp.bool_),
         s((R,), jnp.int32)])


def _gather_full(s):
    Fs = 1 << 12
    return (lambda m, v, f: GE.gather_enrich_pallas(
        m, v, f, derived_dim=D, report_tile=PAPER.flow_tile,
        interpret=False),
        [s((Fs, H, 16), jnp.uint32), s((Fs, H), jnp.bool_),
         s((R,), jnp.int32)])


@pytest.mark.parametrize("build,kernel", [
    (_ingest_block, "ingest_update_block"),
    (_ingest_hbm, "ingest_update_hbm"),
    (_ring_scatter, "ring_scatter"),
    (_ring_scatter_2r, "ring_scatter"),
    (_gather_hbm, "gather_enrich_hbm"),
    (_gather_full, "gather_enrich_full"),
], ids=["ingest_block_E1024", "ingest_hbm_E2^20", "ring_scatter_F2^17",
        "ring_scatter_F2^17_R2^17", "gather_enrich_hbm_F2^17",
        "gather_enrich_full_F2^12"])
def test_main_path_kernel_compiles_for_v5e(one_chip, build, kernel):
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, args = build(s)
    compiled = jax.jit(fn).lower(*args).compile()
    assert kernel in _kernels(compiled)


def test_paper_step_compiles_for_one_v5e_chip(topo, monkeypatch):
    """The whole PAPER dfa_step with backend pallas on one described
    chip: every main-path kernel is compiled in, and the program fits
    the chip's HBM. jax.default_backend() is the CPU here, so the
    pallas-off-TPU refusal is steered off inside this test."""
    monkeypatch.setattr(dispatch, "interpret_flag",
                        lambda b: b == "interpret")
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1),
                ("data", "model"))
    system = DFASystem(dataclasses.replace(PAPER, kernel_backend="pallas"),
                       mesh)
    state = jax.tree.map(
        lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
        jax.eval_shape(system.init_state), system.state_shardings())
    sds, specs = system.event_specs(PAPER.event_block)
    events = {k: jax.ShapeDtypeStruct(v.shape, v.dtype,
                                      sharding=NamedSharding(mesh, specs[k]))
              for k, v in sds.items()}
    now = jax.ShapeDtypeStruct((), jnp.uint32,
                               sharding=NamedSharding(mesh, P()))
    compiled = system.jit_step(donate=True).lower(state, events,
                                                  now).compile()
    kernels = _kernels(compiled)
    for k in ("ingest_update_block", "ring_scatter", "gather_enrich_hbm"):
        assert k in kernels, kernels
    m = compiled.memory_analysis()
    need = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert need < HBM_BYTES, need
