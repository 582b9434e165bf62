# Camera-frame wire codec: 8x8 block DCT, quantized int8, top-K zigzag
# coefficients.
#
# The host->device wire is the scarce resource for camera pipelines (the
# reference ships frames to its CUDA models in-process and never meets
# this constraint; here a host->device hop carries every frame).  Raw
# uint8 RGB is already "compressed" per pixel, so the remaining lever is
# transform coding.  Real JPEG can't be decoded by XLA (entropy-coded
# bitstream), but a FIXED-LAYOUT transform codec can: the host runs a
# blockwise DCT + JPEG-style quantization and ships the first K zigzag
# coefficients as int8; the device dequantizes and inverts the DCT with
# two 8x8 matmuls — static shapes, fully fusible into the consumer
# program (PE_Detect fuses decode+normalize+model into one XLA program,
# the same pattern as the ASR element's mu-law wire).
#
# keep=16 -> 4x fewer wire bytes than raw uint8; keep=10 -> 6.4x.

from __future__ import annotations

import numpy as np

__all__ = ["dct8_encode", "dct8_decode", "dct8_wire_bytes", "DCT_KEEP"]

DCT_KEEP = 16                    # default coefficients kept per block


def _dct_basis() -> np.ndarray:
    """Orthonormal 8x8 DCT-II basis: Y = D @ X @ D.T."""
    k = np.arange(8)[:, None]
    n = np.arange(8)[None, :]
    basis = np.cos((2 * n + 1) * k * np.pi / 16.0)
    basis[0] *= np.sqrt(1.0 / 2.0)
    return (basis * np.sqrt(2.0 / 8.0)).astype(np.float32)


_DCT = _dct_basis()

# JPEG Annex K luminance quantization (quality ~50); shared across
# channels — chroma fidelity matters less for detection than luma
_QUANT = np.array([
    [16, 11, 10, 16, 24, 40, 51, 61],
    [12, 12, 14, 19, 26, 58, 60, 55],
    [14, 13, 16, 24, 40, 57, 69, 56],
    [14, 17, 22, 29, 51, 87, 80, 62],
    [18, 22, 37, 56, 68, 109, 103, 77],
    [24, 35, 55, 64, 81, 104, 113, 92],
    [49, 64, 78, 87, 103, 121, 120, 101],
    [72, 92, 95, 98, 112, 100, 103, 99]], np.float32)


def _zigzag_order() -> np.ndarray:
    """Indices of the 64 block positions in zigzag scan order."""
    order = sorted(((i, j) for i in range(8) for j in range(8)),
                   key=lambda p: (p[0] + p[1],
                                  p[1] if (p[0] + p[1]) % 2 else p[0]))
    return np.array([i * 8 + j for i, j in order], np.int32)


_ZIGZAG = _zigzag_order()


def dct8_wire_bytes(height: int, width: int, channels: int = 3,
                    keep: int = DCT_KEEP) -> int:
    return (height // 8) * (width // 8) * channels * keep


def dct8_encode(image: np.ndarray, keep: int = DCT_KEEP) -> np.ndarray:
    """uint8 [H, W, C] (H, W multiples of 8) -> int8
    [H/8, W/8, C, keep] quantized zigzag DCT coefficients."""
    h, w, c = image.shape
    if h % 8 or w % 8:
        raise ValueError(f"dct8 needs 8-aligned frames, got {h}x{w}")
    x = image.astype(np.float32) - 128.0
    blocks = x.reshape(h // 8, 8, w // 8, 8, c).transpose(0, 2, 4, 1, 3)
    coeffs = np.einsum("ki,bwcij,lj->bwckl", _DCT, blocks, _DCT,
                       optimize=True)
    quantized = np.round(coeffs / _QUANT).reshape(
        h // 8, w // 8, c, 64)[..., _ZIGZAG[:keep]]
    return np.clip(quantized, -127, 127).astype(np.int8)


def dct8_decode(codes, height: int, width: int):
    """int8 [B, H/8, W/8, C, keep] -> float32 [B, H, W, C] in [0, 1].

    jax/XLA path — built from matmuls and a static scatter so the
    consumer program fuses it; runs under jit on TPU."""
    import jax.numpy as jnp

    batch, hb, wb, channels, keep = codes.shape
    flat = jnp.zeros((batch, hb, wb, channels, 64), jnp.float32)
    flat = flat.at[..., _ZIGZAG[:keep]].set(
        codes.astype(jnp.float32))
    coeffs = flat.reshape(batch, hb, wb, channels, 8, 8) * _QUANT
    dct = jnp.asarray(_DCT)
    blocks = jnp.einsum("ik,bwhckl,jl->bwhcij", dct.T, coeffs, dct.T)
    image = (blocks + 128.0).transpose(0, 1, 4, 2, 5, 3).reshape(
        batch, height, width, channels)
    return jnp.clip(image, 0.0, 255.0) / 255.0
