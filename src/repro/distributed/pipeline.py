"""Pipeline parallelism over the "pod" axis (GPipe-style, collective_permute).

At 2+ pods the inter-pod links are the scarcest bandwidth; PP sends only
(microbatch, seq, d_model) activations across pods once per microbatch
instead of all-reducing every gradient. Stages are layer ranges; the
schedule is the classic (num_micro + num_stages - 1)-tick loop with
bubble fraction (S-1)/(M+S-1). This module is mesh-agnostic: it works for
any stage axis, and composes with the FSDP/TP shardings inside each stage.

Used by launch/train.py when --pp is set; equivalence against the plain
path is tested in tests/test_distributed.py.
"""
from __future__ import annotations

from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P


Tree = Any


def pipeline_apply(stage_fn: Callable[[Tree, jax.Array, jax.Array],
                                      jax.Array],
                   stage_params: Tree, x: jax.Array, mesh: Mesh,
                   axis: str = "pod", num_micro: int = 4) -> jax.Array:
    """Run ``x`` (B, S, d) through num_stages = |axis| pipeline stages.

    stage_params: per-stage params ALREADY sharded over ``axis`` (leading
    dim == num_stages, removed inside the shard_map).
    stage_fn(params, x, stage_idx) -> x.
    """
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    if axis not in sizes:
        raise ValueError(
            f"pipeline stage axis {axis!r} is not in mesh axes "
            f"{tuple(mesh.axis_names)}; the 2D DFA meshes name their pod "
            "axis 'pod' (launch.mesh.make_dfa_mesh / "
            "make_production_mesh(multi_pod=True))")
    S = sizes[axis]
    B = x.shape[0]
    assert B % num_micro == 0, (B, num_micro)
    mb = B // num_micro

    other_axes = tuple(a for a in mesh.axis_names if a != axis)

    def local(params, xl):
        """params: (1, ...) stage slice; xl: (B_l, S, d) — replicated over
        the stage axis, sharded over the data axes."""
        params = jax.tree.map(lambda a: a[0], params)
        sid = jax.lax.axis_index(axis)
        assert xl.shape[0] % num_micro == 0 and xl.shape[0] >= num_micro, (
            f"local batch {xl.shape[0]} not divisible into {num_micro} "
            "microbatches")
        micro = xl.reshape(num_micro, xl.shape[0] // num_micro,
                           *xl.shape[1:])
        n_t = num_micro + S - 1
        buf = jnp.zeros_like(micro[0])
        outs = jnp.zeros_like(micro)
        perm = [(i, i + 1) for i in range(S - 1)]

        def tick(carry, t):
            buf, outs = carry
            # stage 0 injects microbatch t (if in range)
            inject = jnp.clip(t, 0, num_micro - 1)
            x_in = jnp.where(sid == 0, micro[inject], buf)
            y = stage_fn(params, x_in, sid)
            # stage s processes microbatch (t - s) when in [0, M)
            m_idx = t - sid
            active = (m_idx >= 0) & (m_idx < num_micro)
            y = jnp.where(active, y, buf)
            # last stage records its finished microbatch
            out_idx = jnp.clip(m_idx, 0, num_micro - 1)
            record = active & (sid == S - 1)
            outs = jnp.where(
                record,
                jax.lax.dynamic_update_index_in_dim(outs, y, out_idx, 0),
                outs)
            # shift activations down the pipe
            buf = jax.lax.ppermute(y, axis, perm)
            return (buf, outs), ()

        (_, outs), _ = jax.lax.scan(tick, (buf, outs), jnp.arange(n_t))
        # replicate final outputs from the last stage to every stage
        outs = jax.lax.psum(
            jnp.where(sid == S - 1, outs, jnp.zeros_like(outs)), axis)
        return outs.reshape(xl.shape)

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P(axis), stage_params),
                  P(other_axes or None)),
        out_specs=P(other_axes or None),
        check_vma=False)
    return fn(stage_params, x)
