"""Fused GELU/SiLU -> MRQ signed two-region quantization Pallas kernel.

The paper's post-GELU MRQ (§III-C) fused into the activation epilogue:
the MLP hidden tile is activated and quantized in VMEM before it is
written back, saving one full HBM round trip of the (tokens, d_ff)
tensor — the largest activation in the block.

Elementwise op: 2-D tiling (bm, bn) aligned to the 8x128 VPU lanes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, sn_ref, sp_ref, o_ref, *, bits: int, kind: str):
    x = x_ref[...].astype(jnp.float32)
    if kind == "gelu":
        h = jax.nn.gelu(x, approximate=True)
    elif kind == "silu":
        h = jax.nn.silu(x)
    else:
        raise ValueError(kind)
    half = 2 ** (bits - 1)
    sn = sn_ref[0, 0]
    sp = sp_ref[0, 0]
    qn = jnp.clip(jnp.round(h / sn), -half, 0) * sn
    qp = jnp.clip(jnp.round(h / sp), 0, half - 1) * sp
    o_ref[...] = jnp.where(h < 0, qn, qp).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bits", "kind", "bm", "bn",
                                             "out_dtype", "interpret"))
def act_mrq(x, s_neg, s_pos, *, bits: int = 8, kind: str = "gelu",
            bm: int = 256, bn: int = 512, out_dtype=jnp.float32,
            interpret=False):
    """act(x) then MRQ signed quant-dequant. x: any shape (>=1d)."""
    shape = x.shape
    N = shape[-1]
    R = 1
    for d in shape[:-1]:
        R *= d
    xm = x.reshape(R, N)
    bm_ = min(bm, max(8, R))
    bn_ = min(bn, max(128, N)) if N >= 128 else N
    Rp = -bm_ * (-R // bm_)
    Np = -bn_ * (-N // bn_)
    xm = jnp.pad(xm, ((0, Rp - R), (0, Np - N)))
    sn = jnp.asarray(s_neg, jnp.float32).reshape(1, 1)
    sp = jnp.asarray(s_pos, jnp.float32).reshape(1, 1)

    out = pl.pallas_call(
        functools.partial(_kernel, bits=bits, kind=kind),
        grid=(Rp // bm_, Np // bn_),
        in_specs=[
            pl.BlockSpec((bm_, bn_), lambda m, n: (m, n)),
            pl.BlockSpec((1, 1), lambda m, n: (0, 0)),
            pl.BlockSpec((1, 1), lambda m, n: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm_, bn_), lambda m, n: (m, n)),
        out_shape=jax.ShapeDtypeStruct((Rp, Np), out_dtype),
        interpret=interpret,
        name="act_mrq",
    )(xm, sn, sp)
    return out[:R, :N].reshape(shape)
