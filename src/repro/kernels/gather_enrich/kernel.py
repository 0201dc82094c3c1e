"""gather_enrich — history gather + feature derivation (Pallas).

Enrichment reads each routed report's (H, 16)-word ring history out of
collector memory and derives its feature vector. Both kernels here share
derived_features' lane-dense math (``derive_rows``: reports on lanes,
history entries on sublanes); they differ in where the gather happens.

``gather_enrich_pallas`` (full-block)
    Collector memory is one un-tiled VMEM block, laid out as word-major
    rows (F, W*H) padded to whole 128-lane tiles; flow ids are
    scalar-prefetched into SMEM and rows are copied into a (T, ·) scratch
    inside the kernel, then transposed so reports ride the lanes. Only
    possible while the shard ring region fits VMEM (reduced configs);
    impossible at Tofino scale — 2^17 flows x 10 x 64 B is ~84 MB against
    ~16 MB of VMEM.

``gather_enrich_hbm_pallas`` (HBM-resident)
    Collector memory stays in HBM and XLA gathers only the R routed
    (H, 16) rows — R x 640 B, a few MB at the paper's R = 4096 — which the
    derived_features kernel then streams through VMEM per report tile.
    VMEM = O(report_tile * H * 16) regardless of F, which is what lets
    one shard own the paper's full 2^17-flow table. The gather is not a
    DMA inside the kernel: Mosaic lays the (F, H, 16) ring out with its
    16-word minor dim padded to 128 lanes and cannot DMA a 16-word slice
    of that tile.

Variant selection (VMEM-budget heuristic + overrides) lives in
repro.kernels.dispatch; both kernels compute bit-identical features.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import wire as WIRE
from repro.kernels.derived_features.kernel import (
    derive_pallas, derive_rows, write_rows)

WORDS = 16
LANES = 128


def _lane_pad(a: jax.Array) -> jax.Array:
    """Pad the minor dim to a whole number of 128-lane tiles."""
    pad = (-a.shape[-1]) % LANES
    return jnp.pad(a, ((0, 0), (0, pad))) if pad else a


# ---------------------------------------------------------------------------
# full-block variant: ring region pinned in VMEM
# ---------------------------------------------------------------------------

def _full_kernel(flows_ref, mem_ref, valid_ref, out_ref, ent_scratch,
                 val_scratch, *, history: int, report_tile: int,
                 derived_dim: int, wire: WIRE.WireFormat):
    base = pl.program_id(0) * report_tile

    def gather(r, _):
        f = flows_ref[base + r]
        ent_scratch[pl.ds(r, 1), :] = mem_ref[pl.ds(f, 1), :]
        val_scratch[pl.ds(r, 1), :] = valid_ref[pl.ds(f, 1), :]
        return 0

    jax.lax.fori_loop(0, report_tile, gather, 0)
    H = history
    ent = ent_scratch[...].T                 # (lanes(W*H), T) word-major
    val = val_scratch[...].T[:H] > 0         # (H, T)
    write_rows(out_ref, derive_rows(lambda w: ent[w * H:(w + 1) * H], val,
                                    derived_dim, wire))


@functools.partial(jax.jit,
                   static_argnames=("derived_dim", "report_tile",
                                    "interpret", "wire"))
def gather_enrich_pallas(memory: jax.Array, entry_valid: jax.Array,
                         local_flow: jax.Array, derived_dim: int = 96,
                         report_tile: int = 128,
                         interpret: bool = True,
                         wire: WIRE.WireFormat = WIRE.V1) -> jax.Array:
    """memory: (F, H, 16) u32; entry_valid: (F, H); local_flow: (R,) i32
    in [0, F) -> (R, derived_dim) f32."""
    F, H, W = memory.shape
    R = local_flow.shape[0]
    assert R % report_tile == 0 and W == WORDS, (R, report_tile, W)
    flows = jnp.clip(local_flow.astype(jnp.int32), 0, F - 1)
    rows = _lane_pad(memory.transpose(0, 2, 1).reshape(F, W * H))
    valid = _lane_pad(entry_valid.astype(jnp.int32))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,            # flows -> SMEM, whole array
        grid=(R // report_tile,),
        in_specs=[
            pl.BlockSpec(rows.shape, lambda r, flows: (0, 0)),
            pl.BlockSpec(valid.shape, lambda r, flows: (0, 0)),
        ],
        out_specs=pl.BlockSpec((derived_dim, report_tile),
                               lambda r, flows: (0, r)),
        scratch_shapes=[
            pltpu.VMEM((report_tile, rows.shape[1]), jnp.uint32),
            pltpu.VMEM((report_tile, valid.shape[1]), jnp.int32),
        ],
    )
    # the pinned ring block (double-buffered by the pipeline) is what
    # this variant is for; give it room beyond the default scoped VMEM
    ring_bytes = 4 * F * (rows.shape[1] + valid.shape[1])
    out = pl.pallas_call(
        functools.partial(_full_kernel, history=H, report_tile=report_tile,
                          derived_dim=derived_dim, wire=wire),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((derived_dim, R), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=2 * ring_bytes + (32 << 20)),
        interpret=interpret,
        name="gather_enrich_full",
    )(flows, rows, valid)
    return out.T


# ---------------------------------------------------------------------------
# HBM-resident variant: ring region stays in HBM, XLA gathers the R rows
# ---------------------------------------------------------------------------

@functools.partial(jax.jit,
                   static_argnames=("derived_dim", "report_tile",
                                    "interpret", "wire"))
def gather_enrich_hbm_pallas(memory: jax.Array, entry_valid: jax.Array,
                             local_flow: jax.Array, derived_dim: int = 96,
                             report_tile: int = 128,
                             interpret: bool = True,
                             wire: WIRE.WireFormat = WIRE.V1) -> jax.Array:
    """Same contract as gather_enrich_pallas, but ``memory``/``entry_valid``
    never enter VMEM as whole blocks: only the R routed rows are read, so
    F is unbounded by VMEM."""
    F, H, W = memory.shape
    R = local_flow.shape[0]
    assert R % report_tile == 0 and W == WORDS, (R, report_tile, W)
    flows = jnp.clip(local_flow.astype(jnp.int32), 0, F - 1)
    return derive_pallas(memory[flows], entry_valid[flows],
                         derived_dim=derived_dim, flow_tile=report_tile,
                         interpret=interpret, wire=wire,
                         name="gather_enrich_hbm")
