"""Pipeline parallelism (GPipe-style) over a mesh axis, shard_map-native.

Each device along ``axis_name`` owns one *stage* (a slice of the layer stack);
microbatches stream through the ring with ``ppermute`` between stages.  With S
stages and M microbatches the schedule runs S + M - 1 ticks; bubble fraction
(S-1)/(S+M-1).  Designed for a mesh axis whose links are the scarce
resource -- the paper's principle again: only the thin activation boundary
crosses the slow link, and it crosses while both sides compute.

The implementation is deliberately simple (no interleaving/looping schedule);
the stage body is an arbitrary jax function.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["pipeline_apply", "bubble_fraction"]


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    return (n_stages - 1) / (n_stages + n_micro - 1)


def pipeline_apply(stage_params, x_micro, stage_fn, axis_name: str):
    """Run a pipelined forward inside shard_map.

    stage_params: this device's stage parameters (already sharded by stage).
    x_micro: [M, mb, ...] microbatches (same replica on every stage device;
             only stage 0 consumes them, the rest arrive by ppermute).
    stage_fn(params, x) -> y: one stage's computation (mb-level).
    Returns [M, mb, ...] outputs valid on the LAST stage device (other stages
    return garbage of the right shape; the caller selects stage S-1).
    """
    s = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    m = x_micro.shape[0]
    ticks = s + m - 1
    perm = [(i, (i + 1) % s) for i in range(s)]

    def tick(carry, t):
        buf, outs = carry  # buf: the activation currently entering this stage
        # stage 0 injects microbatch t (if any); others use the ppermuted buf
        inject = jnp.where(t < m, t, m - 1)
        x_in = jnp.where(idx == 0, x_micro[inject], buf)
        y = stage_fn(stage_params, x_in)
        # pass activations forward around the ring
        buf_next = lax.ppermute(y, axis_name, perm)
        # last stage records its finished microbatch (micro index t - (s-1))
        out_idx = jnp.clip(t - (s - 1), 0, m - 1)
        write = jnp.logical_and(idx == s - 1, t >= s - 1)
        outs = lax.cond(
            write,
            lambda o: lax.dynamic_update_index_in_dim(o, y, out_idx, 0),
            lambda o: o,
            outs,
        )
        return (buf_next, outs), None

    y0 = jax.eval_shape(stage_fn, stage_params, x_micro[0])
    buf0 = jnp.zeros(y0.shape, y0.dtype)
    outs0 = jnp.zeros((m,) + tuple(y0.shape), y0.dtype)
    (_, outs), _ = lax.scan(tick, (buf0, outs0), jnp.arange(ticks))
    # only the last stage holds real outputs; replicate them to every stage so
    # the caller sees a consistent value (one [M, ...]-sized all-reduce).
    outs = jnp.where(idx == s - 1, outs, jnp.zeros_like(outs))
    return lax.psum(outs, axis_name)
