"""Registry client for the gather_enrich op (pipeline stage 6).

Besides backend resolution (ref / pallas / interpret) this wrapper owns
two pieces of shape policy the kernels don't:

* memory-strategy variant selection — ``dispatch.resolve_gather_variant``
  picks the ``full`` kernel (ring pinned in VMEM) while the shard ring
  region fits the VMEM budget and the ``hbm`` path (XLA gathers the R
  routed rows, the derive kernel streams them) beyond, with
  ``DFAConfig.gather_variant`` / ``REPRO_GATHER_VARIANT`` overrides;
* report padding — R is padded up to a multiple of the report tile
  (clamped flow id 0 for pad rows, output rows sliced off) so callers can
  route any report count, power of two or not, without shrinking the tile.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core import wire as WIRE
from repro.kernels import dispatch


def _tile_and_pad(R: int, preferred: int):
    """(tile, padded_R): tile = min(preferred, R), R padded to a multiple.

    Unlike ``negotiate_tile`` (which shrinks the tile to a divisor — fine
    for scatter families that index the whole array) this keeps the tile
    large for awkward R: a prime R costs pad rows, not a degenerate tile.
    """
    t = max(1, min(int(preferred), int(R)))
    pad = (-R) % t
    return t, R + pad


def gather_enrich(memory, entry_valid, local_flow, cfg, backend=None,
                  variant=None):
    """(F,H,16) memory + (F,H) validity + (R,) local flow ids
    -> (R, derived_dim) f32 enriched features, via the selected backend
    and memory-strategy variant."""
    b = dispatch.resolve_backend(backend, cfg)
    if b == "ref":
        _, impl = dispatch.lookup("gather_enrich", "ref", cfg)
        return impl(memory, entry_valid, local_flow, cfg)

    F, H = memory.shape[0], memory.shape[1]
    R = local_flow.shape[0]
    if R == 0:
        return jnp.zeros((0, cfg.derived_dim), jnp.float32)
    rt, Rp = _tile_and_pad(R, dispatch.resolve_report_tile(cfg, R))
    v = dispatch.resolve_gather_variant(variant, cfg, F, H, rt,
                                        cfg.derived_dim)
    family = "gather_enrich" if v == "full" else "gather_enrich_hbm"
    _, impl = dispatch.lookup(family, b, cfg)
    flows = local_flow
    if Rp != R:
        flows = jnp.concatenate(
            [local_flow, jnp.zeros((Rp - R,), local_flow.dtype)])
    out = impl(memory, entry_valid, flows, derived_dim=cfg.derived_dim,
               report_tile=rt, interpret=dispatch.interpret_flag(b),
               wire=WIRE.resolve(cfg))
    return out[:R]
