"""Qm.n fixed-point format math — the paper's quantization scheme (Sec. 4.1).

The paper (Novac et al., Sensors 2021) quantizes with a *uniform, symmetric,
power-of-two* scale factor:

    m = 1 + floor(log2(max_i |x_i|))          (Eq. 1)  integer bits (incl. none)
    n = w - m - 1                             (Eq. 2)  fractional bits
    x_fixed = trunc(x * 2^n)                  (Eq. 3)
    s = 2^-n                                  (Eq. 4)  scale factor

`m` may be negative (leading unused fractional bits reclaimed as precision);
`n` may be negative (very large ranges).  All arithmetic on scale factors is
done on the *exponent* `n` (an int32), so rescaling is an exact bit-shift —
never a floating-point multiply — exactly as on the paper's Cortex-M4 target
and on the TPU integer path.

Everything here is pure jnp and jittable.  Granularity is expressed by the
shape of `n`: scalar (per-tensor / per-network) or a vector broadcast along a
channel axis (per-channel, the paper's declared future work, implemented here
as a beyond-paper extension).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

# Clamp for the fractional-bit exponent.  |n| beyond 30 makes 2^n overflow
# int32 shift semantics and never occurs for sane data; the clamp also handles
# all-zero tensors (max_abs == 0) gracefully.
N_MIN = -30
N_MAX = 30

_INT_DTYPES = {2: jnp.int8, 4: jnp.int8, 8: jnp.int8, 9: jnp.int16, 16: jnp.int16,
               32: jnp.int32}
_ACC_DTYPES = {2: jnp.int32, 4: jnp.int32, 8: jnp.int32, 9: jnp.int32, 16: jnp.int32,
               32: jnp.int64}


def storage_dtype(width: int):
    """Smallest machine integer dtype that holds a `width`-bit value.

    The paper stores int9 (Appendix B) in int16 containers; int4 (beyond-paper)
    packs into int8 containers.
    """
    return _INT_DTYPES[width]


def accumulator_dtype(width: int):
    """2x-operand-width accumulator dtype (paper Sec. 5.8)."""
    return _ACC_DTYPES[width]


def qmin(width: int) -> int:
    return -(2 ** (width - 1))


def qmax(width: int) -> int:
    return 2 ** (width - 1) - 1


def integer_bits(max_abs: jax.Array) -> jax.Array:
    """Eq. 1: required signed-integer bits m for a given max |x|.

    Uses floor(log2(.)) + 1.  For max_abs == 0 the result is driven to a large
    negative value and later clamped via N_MAX.
    """
    max_abs = jnp.asarray(max_abs, jnp.float32)
    safe = jnp.maximum(max_abs, 2.0 ** (-(N_MAX + 1)))
    return 1 + jnp.floor(jnp.log2(safe)).astype(jnp.int32)


def frac_bits_for(max_abs: jax.Array, width: int) -> jax.Array:
    """Eq. 2: fractional bits n = w - m - 1, clamped to [N_MIN, N_MAX]."""
    m = integer_bits(max_abs)
    n = jnp.int32(width) - m - 1
    return jnp.clip(n, N_MIN, N_MAX)


def max_abs(x: jax.Array, axis=None) -> jax.Array:
    """Range statistic used by the paper: max |x| (optionally per-channel)."""
    return jnp.max(jnp.abs(x), axis=axis)


def pow2(n: jax.Array) -> jax.Array:
    """2^n as float32, exact for integer n in [-126, 127].

    Built from the exponent bits: ``jnp.exp2`` is not exact (XLA's CPU
    backend is off by an ulp from |n| = 13 on), and an inexact grid step
    breaks trunc(x * 2^n) * 2^-n being a projection.
    """
    e = jnp.asarray(n).astype(jnp.int32) + 127
    return jax.lax.bitcast_convert_type(e << 23, jnp.float32)


def scale_from_n(n: jax.Array) -> jax.Array:
    """Eq. 4: s = 2^-n, as float32 (used only on the fake-quant/float path)."""
    return pow2(-n)


def quantize(x: jax.Array, n: jax.Array, width: int) -> jax.Array:
    """Eq. 3 + saturation: x_q = sat(trunc(x * 2^n)).

    Truncation (toward zero) matches the paper's `trunc`; saturation matches
    `clamp_to_number_t`.  Returns the storage dtype for `width`.
    """
    xf = x.astype(jnp.float32) * pow2(n)
    xq = jnp.trunc(xf)
    xq = jnp.clip(xq, qmin(width), qmax(width))
    return xq.astype(storage_dtype(width))


def dequantize(xq: jax.Array, n: jax.Array, width: int = 0) -> jax.Array:
    """x = x_q * 2^-n, as float32."""
    del width
    return xq.astype(jnp.float32) * pow2(-n)


def quantize_dequantize(x: jax.Array, n: jax.Array, width: int) -> jax.Array:
    """Fake-quantization: the value set of Qm.n, represented in float.

    This is the forward used during QAT (paper Sec. 4.3: computations stay in
    float but operands are constrained to the quantized value grid).
    """
    xf = x.astype(jnp.float32) * pow2(n)
    xq = jnp.clip(jnp.trunc(xf), qmin(width), qmax(width))
    return xq * pow2(-n)


def requantize(acc: jax.Array, n_in: jax.Array, n_out: jax.Array, width: int) -> jax.Array:
    """Shift a 2x-width accumulator from format n_in to n_out and saturate.

    Paper Sec. 5.8: after an integer multiply the fractional bits of the
    operands add up; the result is shifted right back to the output format and
    saturated to the operand width.  `n_in - n_out` is the right-shift amount;
    implemented as an exact arithmetic shift (with a left shift when the
    output format has more fractional bits).
    """
    shift = (n_in - n_out).astype(jnp.int32)
    shift_b = jnp.broadcast_to(shift, acc.shape)
    # Work at 2x the accumulator width: a left shift may overflow the
    # accumulator *before* saturation (found by hypothesis —
    # tests/test_properties.py::test_requantize_matches_float_semantics).
    # On the MCU/TPU engine this is the SSAT-before-write rule; here the
    # pre-saturation guard compares against qmax >> lshift instead.
    acc64 = acc.astype(jnp.int64)
    rsh = jnp.clip(shift_b, 0, 62)
    lsh = jnp.clip(-shift_b, 0, 62)
    right = jnp.right_shift(acc64, rsh.astype(jnp.int64))
    lim = jnp.right_shift(jnp.int64(qmax(width)), lsh.astype(jnp.int64))
    sat = jnp.where(acc64 >= 0, jnp.int64(qmax(width)), jnp.int64(qmin(width)))
    left = jnp.where(jnp.abs(acc64) > lim, sat,
                     jnp.left_shift(acc64, lsh.astype(jnp.int64)))
    out = jnp.where(shift_b >= 0, right, left)
    out = jnp.clip(out, qmin(width), qmax(width))
    return out.astype(storage_dtype(width))


def align(xq: jax.Array, n_x: jax.Array, n_common: jax.Array, acc_dtype=jnp.int32) -> jax.Array:
    """Align an operand to a common Qm.n before add/sub (paper Sec. 5.8).

    Returns the accumulator dtype; shifts are exact.
    """
    acc = xq.astype(acc_dtype)
    shift = (n_common - n_x).astype(jnp.int32)
    shift_b = jnp.broadcast_to(shift, acc.shape)
    left = jnp.left_shift(acc, jnp.maximum(shift_b, 0))
    right = jnp.right_shift(acc, jnp.maximum(-shift_b, 0))
    return jnp.where(shift_b >= 0, left, right)


@dataclasses.dataclass(frozen=True)
class QTensor:
    """An integerized tensor: storage integers + fractional-bit exponent(s).

    `n` is an int32 scalar (per-tensor) or a vector aligned with `channel_axis`
    (per-channel).  Registered as a pytree so it can live inside param trees,
    be donated, sharded and checkpointed like any other leaf pair.
    """

    q: jax.Array
    n: jax.Array
    width: int
    channel_axis: Optional[int] = None

    def dequantize(self) -> jax.Array:
        n = self.n
        if self.channel_axis is not None and jnp.ndim(n) > 0:
            shape = [1] * self.q.ndim
            shape[self.channel_axis] = -1
            n = n.reshape(shape)
        return dequantize(self.q, n)

    @property
    def shape(self):
        return self.q.shape

    @property
    def nbytes_model(self) -> int:
        """Model-ROM bytes at the *logical* width (paper Table A3 semantics)."""
        return int(np.prod(self.q.shape)) * self.width // 8


# --------------------------------------------------------------------------
# Sub-int8 packed storage (beyond-paper: int4/int2 weight frontier)
# --------------------------------------------------------------------------

def lanes_per_byte(width: int) -> int:
    """How many ``width``-bit lanes fit one int8 container byte (4->2, 2->4)."""
    if width not in (2, 4):
        raise ValueError(f"packed storage supports widths 2 and 4, got {width}")
    return 8 // width


def pack_subint8(q: jax.Array, width: int, axis: int = -2) -> jax.Array:
    """Pack ``width``-bit signed integers along ``axis`` into int8 bytes.

    Lane ``i`` of a byte holds logical element ``lanes*j + i`` in bits
    ``[width*i, width*(i+1))`` (two's complement), so lane 0 is the *low*
    nibble — the layout ``wq4_matmul``'s in-kernel unpack assumes.  A length
    not divisible by the lane count is zero-padded; the pad nibbles
    dequantize to 0 and are sliced away by :func:`unpack_subint8`.
    """
    lanes = lanes_per_byte(width)
    q = jnp.asarray(q)
    ax = axis % q.ndim
    k = q.shape[ax]
    pad = (-k) % lanes
    if pad:
        spec = [(0, 0)] * q.ndim
        spec[ax] = (0, pad)
        q = jnp.pad(q, spec)
    moved = jnp.moveaxis(q, ax, -1).astype(jnp.int32)
    grp = moved.reshape(*moved.shape[:-1], -1, lanes)
    mask = (1 << width) - 1
    acc = jnp.zeros(grp.shape[:-1], jnp.int32)
    for i in range(lanes):
        acc = acc | ((grp[..., i] & mask) << (width * i))
    packed = jax.lax.bitcast_convert_type(acc.astype(jnp.uint8), jnp.int8)
    return jnp.moveaxis(packed, -1, ax)


def unpack_subint8(packed: jax.Array, width: int, k: int, axis: int = -2) -> jax.Array:
    """Inverse of :func:`pack_subint8`: int8 bytes -> ``k`` signed lanes.

    Bit-exact round trip for any value on the ``width``-bit grid and any
    lane alignment (``k`` need not divide the lane count).
    """
    lanes = lanes_per_byte(width)
    ax = axis % packed.ndim
    moved = jnp.moveaxis(packed, ax, -1)
    u = jax.lax.bitcast_convert_type(moved, jnp.uint8).astype(jnp.int32)
    mask = (1 << width) - 1
    half = 1 << (width - 1)
    vals = jnp.stack([(u >> (width * i)) & mask for i in range(lanes)], axis=-1)
    vals = jnp.where(vals >= half, vals - (1 << width), vals)
    flat = vals.reshape(*vals.shape[:-2], -1)[..., :k].astype(jnp.int8)
    return jnp.moveaxis(flat, -1, ax)


def block_frac_bits(x: jax.Array, width: int, block_size: int,
                    axis: int = -2) -> jax.Array:
    """Per-block (MX-style) exponents: Eq. 1-2 over ``block_size`` runs of
    ``axis``.  Returns the exponent grid with ``axis`` shrunk to the number
    of blocks (the trailing partial block, if any, is ranged over its real
    elements only — zero-padding cannot inflate a block's scale).
    """
    ax = axis % x.ndim
    k = x.shape[ax]
    pad = (-k) % block_size
    if pad:
        spec = [(0, 0)] * x.ndim
        spec[ax] = (0, pad)
        x = jnp.pad(x, spec)
    moved = jnp.moveaxis(x, ax, -1)
    grp = moved.reshape(*moved.shape[:-1], -1, block_size)
    ma = jnp.max(jnp.abs(grp), axis=-1)
    return jnp.moveaxis(frac_bits_for(ma, width), -1, ax)


def _qtensor_flatten(t: QTensor):
    return (t.q, t.n), (t.width, t.channel_axis)


def _qtensor_unflatten(aux, children):
    q, n = children
    width, channel_axis = aux
    return QTensor(q=q, n=n, width=width, channel_axis=channel_axis)


jax.tree_util.register_pytree_node(QTensor, _qtensor_flatten, _qtensor_unflatten)


def quantize_tensor(
    x: jax.Array,
    width: int,
    *,
    channel_axis: Optional[int] = None,
    n_override: Optional[jax.Array] = None,
) -> QTensor:
    """Quantize a float tensor to a QTensor per the paper's method (Sec 4.1.4).

    channel_axis=None  -> per-tensor scale (paper's per-layer mode)
    channel_axis=k     -> per-channel scale along axis k (beyond-paper)
    channel_axis=(a,b) -> per-(a,b) scales, e.g. (0, -1) on scan-stacked
                          kernels = per-layer-per-channel (beyond-paper);
                          n is stored broadcast-shaped (kept dims + 1s)
    n_override         -> externally chosen exponent (paper's per-network mode,
                          e.g. Q7.9 => n = 9 for the whole net)
    """
    if n_override is not None:
        n = jnp.asarray(n_override, jnp.int32)
        nb = n
        if isinstance(channel_axis, int) and jnp.ndim(n) > 0:
            shape = [1] * x.ndim
            shape[channel_axis] = -1
            nb = n.reshape(shape)
        return QTensor(quantize(x, nb, width), n, width,
                       channel_axis if isinstance(channel_axis, int) else None)
    if channel_axis is None:
        n = frac_bits_for(max_abs(x), width)
        return QTensor(quantize(x, n, width), n, width, None)
    if isinstance(channel_axis, tuple):
        keep = tuple(a % x.ndim for a in channel_axis)
        axes = tuple(a for a in range(x.ndim) if a not in keep)
        ma = jnp.max(jnp.abs(x), axis=axes, keepdims=True)
        n = frac_bits_for(ma, width)          # broadcast-shaped exponents
        return QTensor(quantize(x, n, width), n, width, None)
    axes = tuple(a for a in range(x.ndim) if a != channel_axis % x.ndim)
    n = frac_bits_for(max_abs(x, axis=axes), width)
    shape = [1] * x.ndim
    shape[channel_axis] = -1
    return QTensor(quantize(x, n.reshape(shape), width), n, width, channel_axis % x.ndim)


@dataclasses.dataclass(frozen=True)
class PackedQTensor:
    """A sub-int8 weight tensor: packed int8 container + pow2 exponents.

    Storage is ``width``-bit (4 or 2) two's-complement lanes packed along the
    *contraction* axis (axis -2 of a ``(..., K, N)`` GEMM weight — see
    :func:`pack_subint8`), so ``q`` has shape ``(..., ceil(K/lanes), N)`` and
    the container holds ``width/8`` bytes per logical element — the ROM /
    HBM-bandwidth halving below int8.

    ``n`` carries the exponents on the paper's pow2 grid:

    * scalar                       — per-tensor
    * ``(..., 1, N)``              — per-output-channel (``block_size=None``)
    * ``(..., ceil(K/bs), N)``     — per-block (MX-style), ``block_size=bs``
      runs of K share one exponent

    Registered as a pytree (``q``/``n`` are children; ``width``, ``k`` and
    ``block_size`` static aux), so packed weights ride param trees, jit
    donation and ``lax.scan`` stacking exactly like :class:`QTensor`.
    """

    q: jax.Array
    n: jax.Array
    width: int
    k: int
    block_size: Optional[int] = None

    @property
    def shape(self):
        """Logical (unpacked) shape ``(..., K, N)``."""
        return (*self.q.shape[:-2], self.k, self.q.shape[-1])

    @property
    def nbytes_packed(self) -> int:
        """Actual container bytes (int8 payload; scales excluded)."""
        return int(np.prod(self.q.shape))

    @property
    def nbytes_model(self) -> int:
        """Model-ROM bytes at the logical width (Table A3 semantics)."""
        return int(np.prod(self.shape)) * self.width // 8

    def unpack(self) -> jax.Array:
        """The int8-held ``width``-bit integers, unpacked to ``(..., K, N)``."""
        return unpack_subint8(self.q, self.width, self.k, axis=-2)

    def scales(self) -> jax.Array:
        """Float ``2^-n`` broadcastable against the unpacked ``(..., K, N)``."""
        n = self.n
        if self.block_size is not None and jnp.ndim(n) > 0:
            n = jnp.repeat(n, self.block_size, axis=-2)[..., : self.k, :]
        return pow2(-jnp.asarray(n))

    def dequantize(self) -> jax.Array:
        """Float reconstruction: unpack * 2^-n (per-channel or per-block)."""
        return self.unpack().astype(jnp.float32) * self.scales()


def _packed_flatten(t: PackedQTensor):
    return (t.q, t.n), (t.width, t.k, t.block_size)


def _packed_unflatten(aux, children):
    q, n = children
    width, k, block_size = aux
    return PackedQTensor(q=q, n=n, width=width, k=k, block_size=block_size)


jax.tree_util.register_pytree_node(PackedQTensor, _packed_flatten, _packed_unflatten)


def quantize_tensor_packed(
    x: jax.Array,
    width: int,
    *,
    block_size: Optional[int] = None,
    per_channel: bool = True,
) -> PackedQTensor:
    """Quantize a ``(..., K, N)`` weight to packed ``width``-bit storage.

    ``block_size=None`` uses one exponent per output channel over the whole
    K axis (the per-channel Qm.n grid at sub-int8 width); ``block_size=bs``
    gives every ``bs``-run of K its own exponent (MX-style block scaling —
    tighter grids where a channel's dynamic range varies along K).
    ``per_channel=False`` with ``block_size=None`` collapses to a single
    per-tensor exponent.
    """
    if x.ndim < 2:
        raise ValueError(f"packed weights need ndim >= 2, got {x.ndim}")
    lanes = lanes_per_byte(width)
    k = x.shape[-2]
    if block_size is not None:
        if block_size < lanes or block_size % lanes:
            raise ValueError(
                f"block_size must be a positive multiple of {lanes} "
                f"(the byte lane count at width {width}), got {block_size}")
        n = block_frac_bits(x, width, block_size, axis=-2)
        nb = jnp.repeat(n, block_size, axis=-2)[..., :k, :]
    elif per_channel:
        n = frac_bits_for(jnp.max(jnp.abs(x), axis=-2, keepdims=True), width)
        nb = n
    else:
        n = frac_bits_for(max_abs(x), width)
        nb = n
    q = quantize(x, nb, width)
    return PackedQTensor(pack_subint8(q, width, axis=-2), n, width, k, block_size)
