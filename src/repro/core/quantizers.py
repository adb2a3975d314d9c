"""Fake-quantization ops with straight-through-estimator gradients.

Paper Sec. 4.3: during QAT the forward pass constrains inputs/weights/biases to
the quantized value grid (while staying in float); the backward pass flows
through the *non-quantized* values.  That is exactly a straight-through
estimator, implemented here with ``jax.custom_vjp``.

Also provides the TFLite-style affine (non-pow2 scale + zero-point) quantizer
that the paper compares against (Sec. 7) — implemented so the comparison in
``benchmarks/quant_accuracy.py`` is runnable, and used by the beyond-paper
``asymmetric`` policy switch.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from . import qformat
from .policy import Granularity, QuantPolicy


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def fake_quant(x: jax.Array, n: jax.Array, width: int) -> jax.Array:
    """quantize->dequantize on the pow2 grid; identity gradient (STE)."""
    return qformat.quantize_dequantize(x, n, width)


def _fq_fwd(x, n, width):
    return qformat.quantize_dequantize(x, n, width), None


def _fq_bwd(width, res, g):
    del width, res
    # STE: pass gradients straight through to x; scale exponents get none.
    return g, None


fake_quant.defvjp(_fq_fwd, _fq_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def fake_quant_affine(x: jax.Array, scale: jax.Array, zero: jax.Array, width: int) -> jax.Array:
    """TFLite-style affine fake-quant: round(x/scale)+zero, clip, dequant."""
    q = jnp.clip(jnp.round(x / scale) + zero, qformat.qmin(width), qformat.qmax(width))
    return (q - zero) * scale


def _fqa_fwd(x, scale, zero, width):
    return fake_quant_affine(x, scale, zero, width), None


def _fqa_bwd(width, res, g):
    del width, res
    return g, None, None


fake_quant_affine.defvjp(_fqa_fwd, _fqa_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def ste_int8_weight(x: jax.Array, keep_axes: tuple, q_constraint=None) -> jax.Array:
    """Weight fake-quant that MATERIALIZES the int8 form (STE backward).

    Unlike :func:`fake_quant` (which stays in float), the forward emits an
    actual int8 tensor + dequant, so under pjit the FSDP gather-to-use
    transition *can* ride the int8 operand — **weight-gather wire ÷4 vs
    f32** (the paper's ROM ÷4 applied to the interconnect; §Perf
    "int8-gather training").  ``q_constraint`` pins the int8 tensor to the
    master's sharding so the reshard edge sits after the s8 convert.
    ``keep_axes``: per-axis grids (e.g. (0, -1) on scan-stacked kernels =
    per-layer-per-channel).
    """
    return _ste_int8_fwd(x, keep_axes, q_constraint)[0]


def _ste_int8_fwd(x, keep_axes, q_constraint):
    t = qformat.quantize_tensor(x, 8, channel_axis=keep_axes or None)
    q = t.q if q_constraint is None else q_constraint(t.q)
    out = (q.astype(jnp.float32)
           * qformat.pow2(-t.n)).astype(x.dtype)
    return out, None


def _ste_int8_bwd(keep_axes, q_constraint, res, g):
    del keep_axes, q_constraint, res
    return (g,)


ste_int8_weight.defvjp(_ste_int8_fwd, _ste_int8_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def fake_quant_blocked(x: jax.Array, width: int, block_size: int,
                       axis: int = -2) -> jax.Array:
    """Sub-int8 fake-quant on a per-block (MX-style) pow2 grid; STE backward.

    Each ``block_size`` run of ``axis`` gets its own Eq. 1-2 exponent from
    the live values, then the run is quantize-dequantized at ``width`` bits
    (2 or 4).  The value set matches :func:`repro.core.qformat.
    quantize_tensor_packed` exactly, so QAT with this forward converges onto
    the grid the packed serving weights will actually store.
    """
    return _fqb_fwd(x, width, block_size, axis)[0]


def _fqb_fwd(x, width, block_size, axis):
    n = qformat.block_frac_bits(jax.lax.stop_gradient(x), width, block_size,
                                axis=axis)
    ax = axis % x.ndim
    nb = jnp.repeat(n, block_size, axis=ax)
    nb = jax.lax.slice_in_dim(nb, 0, x.shape[ax], axis=ax)
    return qformat.quantize_dequantize(x, nb, width), None


def _fqb_bwd(width, block_size, axis, res, g):
    del width, block_size, axis, res
    return (g,)


fake_quant_blocked.defvjp(_fqb_fwd, _fqb_bwd)


def dynamic_frac_bits(
    x: jax.Array, width: int, *, channel_axis: Optional[int] = None
) -> jax.Array:
    """Paper Eq. 1-2 applied to the live tensor (QAT range reassessment).

    The exponent is computed from the current values and treated as
    non-differentiable (it parameterizes the grid, not the function).
    """
    if channel_axis is None:
        ma = qformat.max_abs(jax.lax.stop_gradient(x))
    else:
        axes = tuple(a for a in range(x.ndim) if a != channel_axis % x.ndim)
        ma = qformat.max_abs(jax.lax.stop_gradient(x), axis=axes)
    return qformat.frac_bits_for(ma, width)


def _broadcast_n(n: jax.Array, x: jax.Array, channel_axis: Optional[int]) -> jax.Array:
    if channel_axis is None or jnp.ndim(n) == 0:
        return n
    shape = [1] * x.ndim
    shape[channel_axis % x.ndim] = -1
    return n.reshape(shape)


def quantize_value(
    x: jax.Array,
    policy: QuantPolicy,
    width: int,
    *,
    channel_axis: Optional[int] = None,
    frozen_n: Optional[jax.Array] = None,
) -> jax.Array:
    """Apply the policy's fake-quantization to a float tensor.

    - per-network granularity uses ``policy.network_frac_bits`` (e.g. Q7.9).
    - otherwise the exponent comes from ``frozen_n`` when given (EVAL/PTQ) or
      is reassessed from the live tensor (QAT), per the paper.
    - asymmetric / non-pow2 variants use the affine quantizer.
    """
    if not policy.enabled:
        return x
    if not policy.power_of_two or not policy.symmetric:
        sg = jax.lax.stop_gradient(x)
        if channel_axis is None:
            hi, lo = jnp.max(sg), jnp.min(sg)
        else:
            axes = tuple(a for a in range(x.ndim) if a != channel_axis % x.ndim)
            hi = jnp.max(sg, axis=axes, keepdims=True)
            lo = jnp.min(sg, axis=axes, keepdims=True)
        if policy.symmetric:
            amax = jnp.maximum(jnp.abs(hi), jnp.abs(lo))
            scale = jnp.maximum(amax, 1e-12) / qformat.qmax(width)
            zero = jnp.zeros_like(scale)
        else:
            scale = jnp.maximum(hi - lo, 1e-12) / (qformat.qmax(width) - qformat.qmin(width))
            zero = jnp.round(-lo / scale) + qformat.qmin(width)
        return fake_quant_affine(x, scale, zero, width)

    if policy.granularity is Granularity.PER_NETWORK and policy.network_frac_bits is not None:
        n = jnp.asarray(policy.network_frac_bits, jnp.int32)
    elif frozen_n is not None:
        n = frozen_n
    else:
        ca = channel_axis if policy.granularity is Granularity.PER_CHANNEL else None
        n = dynamic_frac_bits(x, width, channel_axis=ca)
    ca = channel_axis if policy.granularity is Granularity.PER_CHANNEL else None
    return fake_quant(x, _broadcast_n(n, x, ca), width)


def quantize_weight(x, policy: QuantPolicy, *, channel_axis=None, frozen_n=None):
    return quantize_value(
        x, policy, policy.weight_bits, channel_axis=channel_axis, frozen_n=frozen_n
    )


def quantize_activation(x, policy: QuantPolicy, *, frozen_n=None):
    # Activations are always per-tensor (per-layer) in the paper; per-channel
    # activation scales would break the single-shift requantization.
    return quantize_value(x, policy, policy.act_bits, channel_axis=None, frozen_n=frozen_n)
