"""Sinkhorn rounds of the multi-stream residual's mixing map in one kernel.

``models/mla_moe.py hc_maps`` projects ``H_res`` [n, n, rows] (n = 4 streams)
onto doubly stochastic matrices by ``iters`` rounds of row then column
normalisation. As an XLA loop that is 6 tiny launches a round, 120 a mixer and
1,680 a decode step of 7 layers: two thirds of every operation the step runs,
and of every event a device trace of it holds. Where the rows fit one lane
tile (a decode step, a verify bundle) the rounds run here, on a block that
never leaves VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128


def _kernel(x_ref, o_ref, *, iters: int, eps: float):
    def round_(_, r):  # rows first
        r = r / (jnp.sum(r, axis=1, keepdims=True) + eps)
        return r / (jnp.sum(r, axis=0, keepdims=True) + eps)

    o_ref[...] = jax.lax.fori_loop(0, iters, round_, x_ref[...])


def sinkhorn_rounds(res: jax.Array, iters: int, eps: float,
                    interpret: bool = False) -> jax.Array:
    """``iters`` rounds over ``res`` [n, n, rows] f32, rows <= ``LANES``."""
    n, _, rows = res.shape
    x = jnp.pad(res, ((0, 0), (0, 0), (0, LANES - rows)), constant_values=1.0)
    out = pl.pallas_call(
        functools.partial(_kernel, iters=iters, eps=eps),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret,
        # a constant: the custom call's name in a device trace
        name="hc_sinkhorn",
    )(x)
    return out[:, :, :rows]
