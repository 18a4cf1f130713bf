"""Digit-plane encoding — the software image of the paper's 1T1R array.

The paper stores a length-N dataset of W-bit numbers as bit-planes in a
memristor crossbar: one array dimension indexes *numbers*, the other indexes
*digit positions* (MSB first).  A digit read (DR) reads one digit-column of
all numbers at once.

This module provides:

* raw binary encodings for every data type the paper supports
  (unsigned / two's complement / sign-magnitude / IEEE-754 float), producing
  the exact bit matrix the paper's state controller sees — numpy-first,
  since "programming the array" is an offline step in the paper too; and
* order-preserving unsigned *sort keys* (the classic radix transform) used
  by the throughput-mode radix engines; ``sort_key_jnp`` is the jittable
  version used inside models (MoE routing, logit top-k).

Key property (tested): ``sort_key`` order == value order for every format,
so a single unsigned MSB-first walk sorts everything; the dtype-specific
number-exclusion polarity of the paper (S6) is algebraically folded in.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# Data-type tags (paper §2.2.2 / S6).
UNSIGNED = "unsigned"
TWOS = "twos"
SIGNMAG = "signmag"
FLOAT = "float"  # IEEE-754: float16 (W=16) or float32 (W=32)

_FORMATS = (UNSIGNED, TWOS, SIGNMAG, FLOAT)


def _container(width: int):
    if width <= 8:
        return np.uint8
    if width <= 16:
        return np.uint16
    if width <= 32:
        return np.uint32
    if width <= 64:
        return np.uint64
    raise ValueError(f"unsupported width {width}")


def _mask(width: int) -> np.uint64:
    return np.uint64((1 << width) - 1)


def raw_bits(x, width: int, fmt: str) -> np.ndarray:
    """Raw W-bit pattern of ``x`` as unsigned ints — what is physically
    programmed into the 1T1R array (Fig. 2d)."""
    if fmt not in _FORMATS:
        raise ValueError(f"unknown format {fmt!r}")
    x = np.asarray(x)
    if fmt in (UNSIGNED, TWOS) and x.dtype.kind in "ui":
        # integer casts wrap modulo the container's 2**bits, so its low
        # ``width`` bits are those of the 64-bit pattern below, without
        # the 8-byte temporaries
        u = x.astype(_container(width))
        u &= u.dtype.type((1 << width) - 1)
        return u
    if fmt == UNSIGNED:
        u = x.astype(np.uint64) & _mask(width)
    elif fmt == TWOS:
        u = x.astype(np.int64).astype(np.uint64) & _mask(width)
    elif fmt == SIGNMAG:
        i = x.astype(np.int64)
        sign = (i < 0).astype(np.uint64)
        mag = np.abs(i).astype(np.uint64) & _mask(width - 1)
        u = (sign << np.uint64(width - 1)) | mag
    else:  # FLOAT
        if width == 16:
            u = x.astype(np.float16).view(np.uint16).astype(np.uint64)
        elif width == 32:
            u = x.astype(np.float32).view(np.uint32).astype(np.uint64)
        else:
            raise ValueError("float format supports width 16 or 32 only")
    return u.astype(_container(width))


def to_bitplanes(x, width: int, fmt: str) -> np.ndarray:
    """Encode ``x`` (shape (..., N)) into a (..., W, N) uint8 digit-plane
    matrix.  Row 0 = MSB (the first column the paper's DR visits).  Leading
    dims are independent datasets (one memristor bank each)."""
    u = raw_bits(x, width, fmt)      # container dtype: 4-8x less traffic
    # one plane at a time into the output: no (..., W, N) temporaries,
    # whose pages a call would otherwise fault in afresh
    planes = np.empty(u.shape[:-1] + (width, u.shape[-1]), np.uint8)
    for c in range(width):
        row = planes[..., c, :]
        np.right_shift(u, width - 1 - c, out=row, casting="unsafe")
        row &= 1
    return planes


def to_digitplanes(x, width: int, fmt: str, level_bits: int) -> np.ndarray:
    """Radix-2**level_bits digit planes for the multi-level strategy
    (§2.3.3): (..., ceil(W/n), N) uint32, most-significant digit first."""
    pad = (-width) % level_bits
    width_p = width + pad
    u = raw_bits(x, width, fmt).astype(np.uint64)
    ndig = width_p // level_bits
    shifts = (np.arange(ndig - 1, -1, -1, dtype=np.uint64)
              * np.uint64(level_bits))
    digits = ((u[..., None, :] >> shifts[:, None])
              & np.uint64((1 << level_bits) - 1))
    return digits.astype(np.uint32)


def sign_plane(x, width: int, fmt: str) -> np.ndarray:
    """Boolean sign column (MSB) of ``x`` under ``fmt`` — the extra array
    line the paper's sign-magnitude / float periphery watches (S6)."""
    u = raw_bits(x, width, fmt).astype(np.uint64)
    return ((u >> np.uint64(width - 1)) & np.uint64(1)).astype(bool)


def from_bitplanes(planes, fmt: str):
    """Decode a (W, N) digit-plane matrix back to values."""
    planes = np.asarray(planes)
    width = planes.shape[0]
    shifts = np.arange(width - 1, -1, -1, dtype=np.uint64)
    u = np.sum(planes.astype(np.uint64) << shifts[:, None], axis=0)
    return from_raw_bits(u, width, fmt)


def from_raw_bits(u, width: int, fmt: str):
    u = np.asarray(u).astype(np.uint64) & _mask(width)
    if fmt == UNSIGNED:
        return u.astype(np.int64)
    if fmt == TWOS:
        sign = (u >> np.uint64(width - 1)) & np.uint64(1)
        return u.astype(np.int64) - (sign.astype(np.int64) << width)
    if fmt == SIGNMAG:
        sign = (u >> np.uint64(width - 1)) & np.uint64(1)
        mag = (u & _mask(width - 1)).astype(np.int64)
        return np.where(sign == 1, -mag, mag)
    if fmt == FLOAT:
        if width == 16:
            return u.astype(np.uint16).view(np.float16)
        if width == 32:
            return u.astype(np.uint32).view(np.float32)
    raise ValueError(f"unknown format {fmt!r}")


# ---------------------------------------------------------------------------
# Order-preserving sort keys.
# ---------------------------------------------------------------------------


def sort_key(x, width: int, fmt: str) -> np.ndarray:
    """Map values to unsigned keys such that key order == value order."""
    u = raw_bits(x, width, fmt).astype(np.uint64)
    top = np.uint64(1 << (width - 1))
    allm = _mask(width)
    if fmt == UNSIGNED:
        key = u
    elif fmt == TWOS:
        key = u ^ top
    elif fmt in (SIGNMAG, FLOAT):
        sign = (u >> np.uint64(width - 1)) & np.uint64(1)
        key = np.where(sign == 1, u ^ allm, u ^ top)
    else:
        raise ValueError(fmt)
    return key.astype(_container(width))


def key_to_value(key, width: int, fmt: str):
    """Inverse of :func:`sort_key`."""
    k = np.asarray(key).astype(np.uint64)
    top = np.uint64(1 << (width - 1))
    allm = _mask(width)
    if fmt == UNSIGNED:
        u = k
    elif fmt == TWOS:
        u = k ^ top
    elif fmt in (SIGNMAG, FLOAT):
        sign_flag = (k >> np.uint64(width - 1)) & np.uint64(1)
        u = np.where(sign_flag == 0, k ^ allm, k ^ top)
    else:
        raise ValueError(fmt)
    return from_raw_bits(u, width, fmt)


# ---------------------------------------------------------------------------
# Jittable sort keys for in-model use (throughput mode).  Width <= 32, so no
# x64 is required.  float inputs use the IEEE trick; integer inputs flip the
# sign bit.  Returned dtype: uint16 for 16-bit sources, uint32 otherwise.
# ---------------------------------------------------------------------------


def sort_key_jnp(x: jnp.ndarray) -> jnp.ndarray:
    """Order-preserving unsigned key for float32/float16/bfloat16/int32/
    uint32 arrays, pure jnp."""
    dt = x.dtype
    if dt == jnp.float32:
        u = jax.lax.bitcast_convert_type(x, jnp.uint32)
        sign = u >> 31
        return jnp.where(sign == 1, ~u, u ^ jnp.uint32(0x80000000))
    if dt == jnp.float16:
        u = jax.lax.bitcast_convert_type(x, jnp.uint16)
        sign = u >> 15
        return jnp.where(sign == 1, ~u, u ^ jnp.uint16(0x8000))
    if dt == jnp.bfloat16:
        u = jax.lax.bitcast_convert_type(x, jnp.uint16)
        sign = u >> 15
        return jnp.where(sign == 1, ~u, u ^ jnp.uint16(0x8000))
    if dt == jnp.int32:
        return jax.lax.bitcast_convert_type(x, jnp.uint32) ^ jnp.uint32(0x80000000)
    if dt == jnp.uint32:
        return x
    if dt == jnp.int16:
        return jax.lax.bitcast_convert_type(x, jnp.uint16) ^ jnp.uint16(0x8000)
    if dt == jnp.uint16 or dt == jnp.uint8:
        return x
    raise ValueError(f"unsupported dtype {dt}")


def key_to_value_jnp(key: jnp.ndarray, dtype) -> jnp.ndarray:
    """Inverse of :func:`sort_key_jnp` for float/int dtypes."""
    if dtype == jnp.float32:
        sign = key >> 31
        u = jnp.where(sign == 0, ~key, key ^ jnp.uint32(0x80000000))
        return jax.lax.bitcast_convert_type(u, jnp.float32)
    if dtype in (jnp.float16, jnp.bfloat16):
        sign = key >> 15
        u = jnp.where(sign == 0, ~key, key ^ jnp.uint16(0x8000))
        return jax.lax.bitcast_convert_type(u.astype(jnp.uint16), dtype)
    if dtype == jnp.int32:
        return jax.lax.bitcast_convert_type(key ^ jnp.uint32(0x80000000), jnp.int32)
    if dtype in (jnp.uint32, jnp.uint16, jnp.uint8):
        return key.astype(dtype)
    raise ValueError(f"unsupported dtype {dtype}")


def encode_array(x, width: int, fmt: str) -> Tuple[np.ndarray, np.ndarray]:
    """Convenience: (bitplanes, sort_keys) — the "programming" step that
    writes a dataset into the memristor array (paper Fig. 2d)."""
    return to_bitplanes(x, width, fmt), sort_key(x, width, fmt)


# ---------------------------------------------------------------------------
# The device read path.  Engines route every digit-plane matrix they are
# about to consume through read_planes(); normally it is the identity, but
# a fault-injection context (repro.runtime.faults.inject) installs a hook
# here, so device non-idealities — bit errors, stuck cells, dead banks —
# reach every engine through the same interface real conductance noise
# would.  Encoding helpers above stay clean: they model *programming* the
# array, the hook models *reading* it.
# ---------------------------------------------------------------------------

_read_hook = None


def set_read_hook(fn):
    """Install ``fn(planes, *, kind, level_bits, banks) -> planes`` as the
    device read process; returns the previous hook (for restoration)."""
    global _read_hook
    prev = _read_hook
    _read_hook = fn
    return prev


def read_planes(planes, *, kind: str = "bit", level_bits: int = 1,
                banks: Optional[int] = None):
    """One device read of a stored (..., D, N) digit-plane matrix.
    Identity unless a fault-injection hook is installed.  ``kind`` is
    "bit" for binary planes or "digit" for radix-2^n digit planes;
    ``banks`` tells the hook the bank layout (how dead banks map onto
    slices of the number axis) when the caller knows it."""
    hook = _read_hook
    if hook is None:
        return planes
    return hook(planes, kind=kind, level_bits=level_bits, banks=banks)
