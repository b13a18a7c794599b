"""Sinkhorn rounds of the multi-stream residual's mixing map in one kernel.

``models/mla_moe.py hc_maps`` projects ``H_res`` [n, n, rows] (n = 4 streams)
onto doubly stochastic matrices by ``iters`` rounds of row then column
normalisation. As an XLA loop that is 6 tiny launches a round, 120 a mixer and
1,680 a decode step of 7 layers: two thirds of every operation the step runs,
and of every event a device trace of it holds (and of a prefill chunk's: a
faster prefill starts more requests, and a traced run's ``stop_trace`` grows
with the events: PERF.md, PR 32). The rounds run here, on one block that never
leaves VMEM: a decode step's 8 rows, a verify bundle's 56, a chunk group's
1,024.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
# rows one block holds: [4, 4, 8192] f32 is 1 MB of VMEM in and 1 MB out (the
# stream axis of 4 lies on 8 sublanes)
MAX_ROWS = 8192


def _kernel(x_ref, o_ref, *, iters: int, eps: float):
    def round_(_, r):  # rows first
        r = r / (jnp.sum(r, axis=1, keepdims=True) + eps)
        return r / (jnp.sum(r, axis=0, keepdims=True) + eps)

    o_ref[...] = jax.lax.fori_loop(0, iters, round_, x_ref[...])


def sinkhorn_rounds(res: jax.Array, iters: int, eps: float,
                    interpret: bool = False) -> jax.Array:
    """``iters`` rounds over ``res`` [n, n, rows] f32, rows <= ``MAX_ROWS``."""
    n, _, rows = res.shape
    x = jnp.pad(res, ((0, 0), (0, 0), (0, -rows % LANES)), constant_values=1.0)
    out = pl.pallas_call(
        functools.partial(_kernel, iters=iters, eps=eps),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret,
        # a constant: the custom call's name in a device trace
        name="hc_sinkhorn",
    )(x)
    return out[:, :, :rows]
