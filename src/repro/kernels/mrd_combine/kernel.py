"""Pallas TPU fused dequant-accumulate for compressed MRD reduce-scatter.

The receive path of the compressed butterfly does, per stage:
``keep += dequantize(recv_q, recv_scales)``.  Unfused this is int8->f32 cast,
reshape-scale, add — three HBM round-trips over the gradient shard.  The
kernel streams (x, q, scales) blocks through VMEM once.

The flat vectors are viewed as ``[n/256, 256]`` rows, one quantization block
per row, so each row's scale broadcasts along the lanes and no block is ever
reshaped inside the kernel.  Grid: (n / bn,), ``bn/256`` rows per program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

QBLOCK = 256


def _combine_kernel(x_ref, q_ref, s_ref, o_ref):
    # x_ref/q_ref/o_ref: [rows, 256]; s_ref: [rows, 1]
    deq = q_ref[...].astype(jnp.float32) * s_ref[...]
    o_ref[...] = (x_ref[...].astype(jnp.float32) + deq).astype(o_ref.dtype)


def mrd_combine_fwd(x, q, scales, *, bn: int = 32768, interpret: bool = False):
    """x: [n]; q: [n] int8; scales: [n/256] f32 -> x + dequant(q)."""
    n = x.shape[0]
    assert n % QBLOCK == 0, n
    bn = min(bn, n)
    assert bn % QBLOCK == 0 and n % bn == 0, (n, bn)
    rows, br = n // QBLOCK, bn // QBLOCK
    out = pl.pallas_call(
        _combine_kernel,
        grid=(rows // br,),
        in_specs=[
            pl.BlockSpec((br, QBLOCK), lambda i: (i, 0)),
            pl.BlockSpec((br, QBLOCK), lambda i: (i, 0)),
            pl.BlockSpec((br, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((br, QBLOCK), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, QBLOCK), x.dtype),
        interpret=interpret,
    )(x.reshape(rows, QBLOCK), q.reshape(rows, QBLOCK), scales.reshape(rows, 1))
    return out.reshape(n)
