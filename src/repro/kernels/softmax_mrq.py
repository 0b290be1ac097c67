"""Fused softmax -> MRQ two-region quantization Pallas kernels.

The paper quantizes post-softmax attention probabilities with MRQ
(§III-C). Fusing the quantizer into the softmax epilogue means the
probability tile never round-trips to HBM in full precision. Two
variants:

``softmax_mrq``
    The fidelity variant: emits the quant-DEQUANTIZED fp tile (feeds a
    full-precision P·V, halves the probs traffic vs a separate qdq
    pass).

``softmax_mrq_codes``
    The deployment variant: emits the int8 CODES the ``int8_bmm_pv``
    kernel consumes directly, with the two MRQ regions packed into one
    signed byte — code c >= 0 is the region-1 (fine step s1) code,
    c < 0 stores the NEGATED region-2 (coarse step s2 = 1/2^{k-1})
    code, so region-2's full [0, 2^{k-1}] code range fits. The only
    overlap, c == 0, dequantizes to exactly 0 under either region, so
    the encoding is lossless. ``s1`` is TGQ-stacked (G, 1) and the
    timestep group is scalar-prefetched like the int8 matmul kernels —
    one compiled executable across all groups. Probs traffic drops
    4x: int8 write + int8 read instead of fp32 write + fp32 read.

Region select is branch-free (both-region compute + mask select), which
vectorizes on the 8x128 VPU lanes — the TPU adaptation of the paper's
per-element region branch.

Tiling: rows of the (R, C) score matrix are tiled (br rows per step);
each step holds the full C (key) extent in VMEM for an exact softmax
(rows up to C = 32k fit: 128 x 32k x 4B = 16MB/2 with br=64; default
br=256 targets C <= 4k).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.int8_matmul import _group_param, _stack3


def _kernel(s_ref, s1_ref, o_ref, *, bits: int):
    x = s_ref[...].astype(jnp.float32)
    x = x - jnp.max(x, axis=-1, keepdims=True)
    e = jnp.exp(x)
    p = e / jnp.sum(e, axis=-1, keepdims=True)

    half = 2 ** (bits - 1)
    s1 = s1_ref[0, 0]
    s2 = 1.0 / half
    q1 = jnp.clip(jnp.round(p / s1), 0, half - 1) * s1
    q2 = jnp.clip(jnp.round(p / s2), 0, half) * s2
    o_ref[...] = jnp.where(p < half * s1, q1, q2).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bits", "br", "out_dtype",
                                             "interpret"))
def softmax_mrq(scores, s1, *, bits: int = 8, br: int = 256,
                out_dtype=jnp.float32, interpret=False):
    """Row-softmax over the LAST axis then MRQ quant-dequant.

    scores: (..., C); s1: scalar (already TGQ-selected for the current
    timestep group). Returns quantized probabilities, same shape.
    """
    shape = scores.shape
    C = shape[-1]
    R = 1
    for d in shape[:-1]:
        R *= d
    x = scores.reshape(R, C)
    br_ = min(br, max(8, R))
    Rp = -br_ * (-R // br_)
    x = jnp.pad(x, ((0, Rp - R), (0, 0)))
    s1 = jnp.asarray(s1, jnp.float32).reshape(1, 1)

    out = pl.pallas_call(
        functools.partial(_kernel, bits=bits),
        grid=(Rp // br_,),
        in_specs=[
            pl.BlockSpec((br_, C), lambda r: (r, 0)),
            pl.BlockSpec((1, 1), lambda r: (0, 0)),
        ],
        out_specs=pl.BlockSpec((br_, C), lambda r: (r, 0)),
        out_shape=jax.ShapeDtypeStruct((Rp, C), out_dtype),
        interpret=interpret,
        name="softmax_mrq",
    )(x, s1)
    return out[:R].reshape(shape)


def _codes_kernel(g_ref, s_ref, s1_ref, o_ref, *, bits: int):
    """Softmax rows then emit region-signed int8 MRQ codes (no dequant)."""
    del g_ref                       # consumed by the s1 index map
    x = s_ref[...].astype(jnp.float32)
    x = x - jnp.max(x, axis=-1, keepdims=True)
    e = jnp.exp(x)
    p = e / jnp.sum(e, axis=-1, keepdims=True)

    half = 2 ** (bits - 1)
    s1 = s1_ref[0, 0]
    s2 = 1.0 / half
    q1 = jnp.clip(jnp.round(p / s1), 0, half - 1)
    q2 = jnp.clip(jnp.round(p / s2), 0, half)
    o_ref[...] = jnp.where(p < half * s1, q1, -q2).astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("bits", "br", "interpret"))
def softmax_mrq_codes(scores, s1, g=None, *, bits: int = 8, br: int = 256,
                      interpret=False):
    """Row-softmax over the LAST axis then MRQ quantization to CODES.

    scores: (..., C); s1: (G, 1) f32 TGQ-stacked region-1 steps; g: the
    timestep group (python int or traced scalar — scalar-prefetched, so
    a traced g changes which s1 row streams in, never the executable).
    Returns int8 region-signed codes, same shape as ``scores``: c >= 0
    is a region-1 code (value c*s1), c < 0 a negated region-2 code
    (value -c*s2). ``int8_bmm_pv`` consumes these directly.
    """
    shape = scores.shape
    C = shape[-1]
    R = 1
    for d in shape[:-1]:
        R *= d
    x = scores.reshape(R, C)
    br_ = min(br, max(8, R))
    Rp = -br_ * (-R // br_)
    x = jnp.pad(x, ((0, Rp - R), (0, 0)))
    G = s1.shape[0]
    assert s1.shape == (G, 1), s1.shape
    if g is None:
        g = 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(Rp // br_,),
        in_specs=[
            pl.BlockSpec((br_, C), lambda r, g: (r, 0)),
            _group_param((1,), lambda r, g: (g[0], 0, 0)),    # s1[g]
        ],
        out_specs=pl.BlockSpec((br_, C), lambda r, g: (r, 0)),
    )
    out = pl.pallas_call(
        functools.partial(_codes_kernel, bits=bits),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Rp, C), jnp.int8),
        interpret=interpret,
        name="softmax_mrq_codes",
    )(jnp.asarray(g, jnp.int32).reshape(1), x, _stack3(s1.astype(jnp.float32)))
    return out[:R].reshape(shape)


def _codes_vec_kernel(gv_ref, s_ref, s1_ref, o_ref, *, bits: int):
    """Vector-tgroup ``_codes_kernel``: each ROW quantizes with its own
    group's s1, gathered exactly from the full (G, 1) stack (deferred
    import dodges the int8_fused <-> softmax cycle risk at package init —
    there is none today, but keep the dep one-way)."""
    from repro.kernels.int8_fused import _gather_rows
    x = s_ref[...].astype(jnp.float32)
    x = x - jnp.max(x, axis=-1, keepdims=True)
    e = jnp.exp(x)
    p = e / jnp.sum(e, axis=-1, keepdims=True)

    half = 2 ** (bits - 1)
    s1_row = _gather_rows(gv_ref[...], s1_ref[...])       # (br, 1)
    s2 = 1.0 / half
    q1 = jnp.clip(jnp.round(p / s1_row), 0, half - 1)
    q2 = jnp.clip(jnp.round(p / s2), 0, half)
    o_ref[...] = jnp.where(p < half * s1_row, q1, -q2).astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("bits", "br", "interpret"))
def softmax_mrq_codes_vec(scores, s1, gv=None, *, bits: int = 8,
                          br: int = 256, interpret=False):
    """``softmax_mrq_codes`` with a per-ROW group vector.

    scores: (..., C); gv: int32 with shape ``scores.shape[:-1]`` (one
    group per softmax row — batched callers pass the slot's group
    repeated over heads/queries). The full (G, 1) s1 stack streams and
    each row gathers its own step in VMEM; a constant gv is bit-identical
    to the scalar-prefetch path.
    """
    shape = scores.shape
    C = shape[-1]
    R = 1
    for d in shape[:-1]:
        R *= d
    x = scores.reshape(R, C)
    br_ = min(br, max(8, R))
    Rp = -br_ * (-R // br_)
    x = jnp.pad(x, ((0, Rp - R), (0, 0)))
    G = s1.shape[0]
    assert s1.shape == (G, 1), s1.shape
    gv = (jnp.zeros((R,), jnp.int32) if gv is None
          else jnp.asarray(gv, jnp.int32).reshape(R))
    gv = jnp.pad(gv, (0, Rp - R)).reshape(Rp, 1)

    out = pl.pallas_call(
        functools.partial(_codes_vec_kernel, bits=bits),
        grid=(Rp // br_,),
        in_specs=[
            pl.BlockSpec((br_, 1), lambda r: (r, 0)),         # gv rows
            pl.BlockSpec((br_, C), lambda r: (r, 0)),
            pl.BlockSpec((G, 1), lambda r: (0, 0)),           # s1 stack
        ],
        out_specs=pl.BlockSpec((br_, C), lambda r: (r, 0)),
        out_shape=jax.ShapeDtypeStruct((Rp, C), jnp.int8),
        interpret=interpret,
        name="softmax_mrq_codes_vec",
    )(gv, x, s1.astype(jnp.float32))
    return out[:R].reshape(shape)
