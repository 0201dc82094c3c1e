"""ring_scatter — RDMA-WRITE placement into the Fig-4 ring buffer (Pallas).

The GPUDirect analogue: payloads are written VERBATIM at translator-computed
(flow, history) coordinates, in report order (last write wins), directly in
device memory. The collector tile (flow_tile, H, 16 words) is pinned in VMEM
while a sequential fori_loop replays the payload stream — matching the
ordering semantics of RDMA WRITE-Only onto a queue pair. The buffer is
donated/aliased so placement is genuinely in-place (no staging copy — the
exact property Fig 9 measures DFA against).

Grid: (flow_tiles,). Payload count is the sequential dimension; payloads not
belonging to the tile are masked stores.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

WORDS = 16


def _kernel(coords_ref, payload_ref, mem_in_ref, mem_out_ref, *,
            flow_tile: int, history: int):
    ft = pl.program_id(0)
    base = ft * flow_tile
    mem_out_ref[...] = mem_in_ref[...]
    R = payload_ref.shape[0]

    def body(r, _):
        flow = coords_ref[r, 0] - base
        hist = coords_ref[r, 1]
        ok = jnp.logical_and(flow >= 0, flow < flow_tile)
        ok = jnp.logical_and(ok, coords_ref[r, 2] > 0)

        @pl.when(ok)
        def _store():
            row = payload_ref[r, :]
            mem_out_ref[flow, hist, :] = row
        return 0

    jax.lax.fori_loop(0, R, body, 0)


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
    coords = jnp.stack([flow.astype(jnp.int32), hist.astype(jnp.int32),
                        mask.astype(jnp.int32)], axis=1)      # (R, 3)

    out = pl.pallas_call(
        functools.partial(_kernel, flow_tile=flow_tile, history=H),
        grid=(F // flow_tile,),
        in_specs=[
            pl.BlockSpec((R, 3), lambda f: (0, 0)),
            pl.BlockSpec((R, WORDS), lambda f: (0, 0)),
            pl.BlockSpec((flow_tile, H, WORDS), lambda f: (f, 0, 0)),
        ],
        out_specs=pl.BlockSpec((flow_tile, H, WORDS), lambda f: (f, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((F, H, WORDS), jnp.uint32),
        input_output_aliases={2: 0},
        interpret=interpret,
        name="ring_scatter",
    )(coords, payloads, memory)
    return out
