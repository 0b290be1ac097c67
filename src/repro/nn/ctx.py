"""Op context — the interception seam between models and the PTQ engine.

Every model in ``repro.models`` routes matmul-like computations and
quantization-relevant activations through an :class:`OpContext`:

- ``linear(name, x, w, b)``      — activation × weight projections,
- ``einsum(name, spec, a, b)``   — activation × activation MatMuls
                                   (attention QK^T and P·V),
- ``act(name, x, kind)``         — identity hook on distributions the paper
                                   treats specially (``post_softmax``,
                                   ``post_gelu``, ``post_silu``),
- ``attention(name, q, k, v)``   — the whole QK^T → softmax → P·V block.
                                   The DEFAULT implementation composes the
                                   three seams above (so recording /
                                   calibration / tap contexts keep seeing
                                   the individual ``{name}/qk``,
                                   ``{name}/probs`` and ``{name}/pv`` ops),
                                   while ``QuantContext(kernel=True)``
                                   overrides it to lower the block onto the
                                   int8 attention Pallas kernels — exactly
                                   how ``ctx.linear`` sites lower to
                                   ``int8_matmul_fq``.

``FPContext`` is the no-op full-precision implementation. The PTQ engine
(`repro.core`) provides:

- ``CalibrationContext`` — records activation ranges / histograms and
  (in a second pass) Fisher weights per op name,
- ``QuantContext``       — applies the calibrated quantizers, either as
  simulated quant-dequant (fidelity experiments) or via the int8 Pallas
  kernels (deployment path),

without any change to model code. ``name`` uniquely identifies the op
within a layer; when models run their blocks in a Python loop the layer
index is baked into the name (``blk3/attn/qk``), and when they run under
``lax.scan`` the name is layer-invariant and contexts receive stacked
per-layer parameters plus a traced ``layer`` index (see
``OpContext.at_layer``).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e9          # additive mask value for attention scores


def apply_norm_mod(x, norm_mod, eps: float = 1e-6):
    """Reference adaLN norm-modulate chain for the ``ctx.linear`` seam.

    ``norm_mod = (shift, scale)`` with per-BATCH (B, K) rows; x carries a
    leading batch axis. Computes the non-affine layernorm (the exact op
    sequence of ``layers.layernorm_apply`` — mean, var, ``lax.rsqrt(var +
    eps)``) followed by ``y * (1 + scale) + shift``. Contexts that do NOT
    lower to kernels run this in fp; ``QuantContext(kernel=True)`` passes
    the rows to the fused kernels, whose VMEM prologue replays the same
    ops (bit-identical — asserted by the conformance suite)."""
    if norm_mod is None:
        return x
    shift, scale = norm_mod
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    bshape = (shift.shape[0],) + (1,) * (x.ndim - 2) + (shift.shape[-1],)
    return y * (1.0 + scale.reshape(bshape)) + shift.reshape(bshape)


def apply_gate_residual(y, gate_residual):
    """Reference adaLN gate + residual epilogue for ``ctx.linear``.

    ``gate_residual = (gate, residual)`` with gate (B, N) rows and a
    y-shaped residual: returns ``residual + gate * y``. The kernel path
    fuses this into the dequant epilogue ahead of the single HBM write."""
    if gate_residual is None:
        return y
    gate, res = gate_residual
    bshape = (gate.shape[0],) + (1,) * (y.ndim - 2) + (gate.shape[-1],)
    return res + gate.reshape(bshape) * y


@dataclasses.dataclass
class OpContext:
    """Base class. ``tgroup`` is the TGQ timestep-group index — a traced
    scalar, a per-slot (B,) int32 VECTOR (vector-tgroup batched path: one
    forward over a batch whose slots sit at different timesteps; quantized
    contexts gather each batch row's group params), or None outside
    diffusion. ``layer`` is the current layer index when the caller runs
    blocks under ``lax.scan`` (traced scalar) or a concrete int.
    """

    tgroup: Optional[Any] = None
    layer: Optional[Any] = None

    def at_layer(self, layer) -> "OpContext":
        return dataclasses.replace(self, layer=layer)

    def with_tgroup(self, tgroup) -> "OpContext":
        return dataclasses.replace(self, tgroup=tgroup)

    def split_arrays(self):
        """``(arrays, rebuild)``: the context's array state, and a function
        that returns this context with replacements for those arrays.

        A jitted caller passes ``arrays`` as arguments and rebuilds the
        context inside the traced function: an array the function closes
        over would be compiled into the program as a constant (for a
        quantized model, every weight code). This context holds none."""
        return (), lambda arrays: self

    # -- op seams ----------------------------------------------------------
    def linear(self, name: str, x, w, b=None, norm_mod=None,
               gate_residual=None):
        """Projection seam. ``norm_mod=(shift, scale)`` asks the context
        to apply the adaLN layernorm-modulate chain to x first;
        ``gate_residual=(gate, residual)`` asks it to finish with
        ``residual + gate * y``. Passing them through the seam (instead
        of computing them in the model) lets kernel-lowering contexts
        fuse both into the matmul's VMEM prologue/epilogue; every other
        context applies the fp reference helpers above."""
        raise NotImplementedError

    def einsum(self, name: str, spec: str, a, b, b_is_weight: bool = False):
        """General matmul seam. ``b_is_weight`` marks operand b as a
        parameter tensor (e.g. stacked per-expert weights) so quantized
        contexts use a weight quantizer (per-channel) for it."""
        raise NotImplementedError

    def act(self, name: str, x, kind: str):
        raise NotImplementedError

    def attention(self, name: str, q, k, v, *, mask=None, scale=1.0):
        """Grouped scaled-dot-product attention seam.

        q: (B, Sq, Hk, G, hd); k, v: (B, Skv, Hk, hd); ``mask``
        broadcastable to (B, Hk, G, Sq, Skv) boolean (True = attend) or
        None. Returns (B, Sq, Hk, G, hd).

        This default composes the three fine-grained seams — the op
        names ``{name}/qk``, ``{name}/probs``, ``{name}/pv`` are the
        contract every PTQ context keys on. Contexts that lower the
        whole block to a fused kernel override this method but keep the
        same names for their packed parameters.
        """
        scores = self.einsum(f"{name}/qk", "bqhgd,bkhd->bhgqk", q, k) * scale
        if mask is not None:
            scores = jnp.where(mask, scores, NEG_INF)
        probs = jax.nn.softmax(scores.astype(jnp.float32),
                               axis=-1).astype(q.dtype)
        probs = self.act(f"{name}/probs", probs, "post_softmax")
        return self.einsum(f"{name}/pv", "bhgqk,bkhd->bqhgd", probs, v)


@dataclasses.dataclass
class FPContext(OpContext):
    """Full-precision passthrough (the default for training and FP eval)."""

    def linear(self, name, x, w, b=None, norm_mod=None, gate_residual=None):
        x = apply_norm_mod(x, norm_mod)
        y = x @ w
        if b is not None:
            y = y + b
        return apply_gate_residual(y, gate_residual)

    def einsum(self, name, spec, a, b, b_is_weight=False):
        return jnp.einsum(spec, a, b)

    def act(self, name, x, kind):
        return x
