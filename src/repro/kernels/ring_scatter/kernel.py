"""ring_scatter — RDMA-WRITE placement into the Fig-4 ring buffer (Pallas).

The GPUDirect analogue: payloads are written VERBATIM at translator-computed
(flow, history) coordinates, in report order (last write wins), directly in
device memory. The collector tile (flow_tile, H, 16 words) is pinned in VMEM
while a sequential loop replays the tile's own payloads — matching the
ordering semantics of RDMA WRITE-Only onto a queue pair. The buffer is
donated/aliased so placement is genuinely in-place (no staging copy — the
exact property Fig 9 measures DFA against).

Grid: (flow_tiles,). Before the kernel, XLA orders the reports by flow tile
with a stable sort (report order is kept within a tile, so last-write-wins
per (flow, hist) is unchanged) and finds each tile's range of the sorted
stream. Tile f visits only rows [off[f], off[f+1]): the offsets are
scalar-prefetched into SMEM, and the rows arrive in CHUNK-row pieces by
double-buffered DMA from HBM — each payload chunk into VMEM, each chunk of
packed (flow-in-tile, hist) keys into SMEM. VMEM holds the tile's ring block
and two payload chunks, whatever R is.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

WORDS = 16
LANES = 128
CHUNK = 1024         # sorted rows per DMA; 1D DMAs need >= 1024 elements


def _kernel(off_ref, key_hbm, pay_hbm, mem_in_ref, mem_out_ref, key_s,
            pay_s, sems, *, hist_bits: int):
    ft = pl.program_id(0)
    mem_out_ref[...] = mem_in_ref[...]
    lo, hi = off_ref[ft], off_ref[ft + 1]
    shift = CHUNK.bit_length() - 1
    c0 = lo >> shift
    c1 = (hi + CHUNK - 1) >> shift            # chunks [c0, c1) hold the range

    def fetch(c, buf):
        rows = pl.ds(pl.multiple_of(c * CHUNK, CHUNK), CHUNK)
        return (pltpu.make_async_copy(
                    key_hbm.at[rows],
                    key_s.at[pl.ds(pl.multiple_of(buf * CHUNK, CHUNK),
                                   CHUNK)],
                    sems.at[0, buf]),
                pltpu.make_async_copy(pay_hbm.at[rows], pay_s.at[buf],
                                      sems.at[1, buf]))

    @pl.when(hi > lo)
    def _place():
        for dma in fetch(c0, 0):
            dma.start()

        def chunk(c, _):
            buf = (c - c0) & 1
            for dma in fetch(c, buf):
                dma.wait()

            @pl.when(c + 1 < c1)
            def _next():
                for dma in fetch(c + 1, 1 - buf):
                    dma.start()

            def row(i, _):
                key = key_s[buf * CHUNK + i]
                mem_out_ref[key >> hist_bits,
                            key & ((1 << hist_bits) - 1), :] = \
                    pay_s[buf, i, pl.ds(0, WORDS)]
                return 0

            base = c * CHUNK
            jax.lax.fori_loop(jnp.maximum(lo, base) - base,
                              jnp.minimum(hi, base + CHUNK) - base, row, 0)
            return 0

        jax.lax.fori_loop(c0, c1, chunk, 0)


@functools.partial(jax.jit,
                   static_argnames=("flow_tile", "history", "interpret"))
def ring_scatter_pallas(memory: jax.Array, payloads: jax.Array,
                        flow: jax.Array, hist: jax.Array, mask: jax.Array,
                        flow_tile: int = 512, history: int = 10,
                        interpret: bool = True) -> jax.Array:
    """memory: (F, H, 16) u32; payloads: (R, 16) u32; flow/hist: (R,) i32.

    Returns updated memory (donation-aliased: in-place on device)."""
    F, H, W = memory.shape
    R = payloads.shape[0]
    assert F % flow_tile == 0 and W == WORDS
    n_tiles = F // flow_tile
    hist_bits = max(H - 1, 1).bit_length()
    flow = flow.astype(jnp.int32)
    hist = hist.astype(jnp.int32)
    # order the reports by flow tile (stable: report order within a tile);
    # masked rows and flows off the ring sort last, past every tile
    tile = jnp.where(mask & (flow >= 0) & (flow < F), flow // flow_tile,
                     n_tiles)
    order = jnp.argsort(tile, stable=True)
    off = jnp.searchsorted(tile[order], jnp.arange(n_tiles + 1),
                           side="left").astype(jnp.int32)
    order = jnp.pad(order, (0, -R % CHUNK))   # whole chunks for the DMAs
    key = ((flow % flow_tile) << hist_bits | hist)[order]
    # payload rows padded to whole 128-lane tiles: a DMA moves whole tiles
    pay = jnp.pad(payloads[order], ((0, 0), (0, LANES - WORDS)))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,            # off -> SMEM, whole array
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((flow_tile, H, WORDS), lambda f, off: (f, 0, 0)),
        ],
        out_specs=pl.BlockSpec((flow_tile, H, WORDS),
                               lambda f, off: (f, 0, 0)),
        scratch_shapes=[
            pltpu.SMEM((2 * CHUNK,), jnp.int32),
            pltpu.VMEM((2, CHUNK, LANES), jnp.uint32),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
    )
    # the ring block in and out, double-buffered, in (8, 128)-word tiles
    block_bytes = flow_tile * -(-H // 8) * 8 * LANES * 4
    chunk_bytes = 2 * CHUNK * LANES * 4
    return pl.pallas_call(
        functools.partial(_kernel, hist_bits=hist_bits),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((F, H, WORDS), jnp.uint32),
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=4 * block_bytes + chunk_bytes + (4 << 20)),
        interpret=interpret,
        name="ring_scatter",
    )(off, key, pay, memory)
