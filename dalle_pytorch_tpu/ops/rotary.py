"""Rotary position embeddings, TPU-native.

Re-implements (from scratch, in JAX) the rotary scheme the reference composes out
of the external ``rotary-embedding-torch`` package: 1-D language frequencies,
2-D axial "pixel" frequencies, and the DALL-E-specific 3-part head-dim split in
which text positions carry 1-D rotary angles and image positions carry 2-D
axial angles, with each modality pinned to a far-away constant position in the
other modality's coordinate system (reference: transformer.py:196-224,
attention.py:32-35).

Everything here is a pure function over static shapes: the full angle table for
a (text + image) sequence is precomputed once at model-build time and indexed
inside the compiled step, so nothing in the hot path is data-dependent.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def lang_freqs(dim: int, theta: float = 10000.0) -> np.ndarray:
    """1-D rotary frequency ladder for token positions (dim//2 frequencies)."""
    return 1.0 / (theta ** (np.arange(0, dim, 2)[: dim // 2] / dim))


def pixel_freqs(dim: int, max_freq: float = 10.0) -> np.ndarray:
    """Frequencies for continuous pixel coordinates in [-1, 1]."""
    return np.linspace(1.0, max_freq / 2, dim // 2) * np.pi


def angles(positions: np.ndarray, freqs: np.ndarray) -> np.ndarray:
    """Outer product position x freq, each frequency repeated twice
    (interleaved) so the angle table lines up with adjacent rotation pairs.

    Returns shape (*positions.shape, 2 * len(freqs)).
    """
    a = np.einsum("...i,j->...ij", np.asarray(positions, dtype=np.float64), freqs)
    return np.repeat(a, 2, axis=-1).reshape(*positions.shape, -1)


@functools.lru_cache(maxsize=None)
def _rotate_half_matrix(d: int) -> np.ndarray:
    """(d, d) signed-permutation matrix P with (x @ P) = rotate_half(x)."""
    P = np.zeros((d, d), dtype=np.float32)
    idx = np.arange(0, d, 2)
    P[idx + 1, idx] = -1.0  # out[2i] = -x[2i+1]
    P[idx, idx + 1] = 1.0   # out[2i+1] = x[2i]
    return P


def rotate_half(x: jnp.ndarray) -> jnp.ndarray:
    """Per adjacent pair (x1, x2) -> (-x2, x1).

    Implemented as a tiny constant signed-permutation matmul rather than a
    pair reshape/stack: each output element is exactly +-one finite input
    element (every other product is exactly 0.0), so the result matches the
    reshape formulation exactly — but the contraction runs over the
    minor-most dim on the MXU and keeps the tensor's layout, where the
    (d//2, 2) reshape forces XLA into n-minor layouts and several ms/step
    of layout-conversion copies at the flagship config. Precision.HIGHEST
    keeps f32 inputs exact (it is a no-op for bf16). Trade-off: a
    non-finite input channel (inf/nan — training already diverged) spreads
    NaN across its whole head-dim row via 0*inf, where the reshape kept it
    in its own pair."""
    assert x.shape[-1] % 2 == 0, f"rotate_half needs an even dim, got {x.shape[-1]}"
    P = jnp.asarray(_rotate_half_matrix(x.shape[-1]), x.dtype)
    return jnp.einsum("...i,ij->...j", x, P, precision=jax.lax.Precision.HIGHEST)


def cos_sin(angle_table, dtype):
    """(cos, sin) of an angle table in ``dtype``: taken of the FLOAT32 angles,
    only the results cast. An angle rounded to bfloat16 first is off by up
    to 16 rad at position 4,095 and frequency 1 (bfloat16 steps by 16-32
    there), which no rotation survives; a cosine rounded afterwards is off by
    2**-9. The one cast for the unfused path (``apply_rotary_emb``) and the
    fused kernel's operands (``ops/flash_attention.py:_rot_tables``), so the
    two agree bit for bit in float32."""
    angle_table = jnp.asarray(angle_table, jnp.float32)
    return jnp.cos(angle_table).astype(dtype), jnp.sin(angle_table).astype(dtype)


def apply_rotary_emb(angle_table: jnp.ndarray, t: jnp.ndarray) -> jnp.ndarray:
    """Rotate the leading ``angle_table.shape[-1]`` channels of ``t``.

    angle_table: (..., n, rot_dim) broadcastable to t's (..., n, d) prefix.
    Channels past rot_dim pass through untouched (the reference rotates only
    3 * (dim_head // 3 // 2 * 2) of every head's channels).
    """
    rot_dim = angle_table.shape[-1]
    cos, sin = cos_sin(angle_table, t.dtype)
    if rot_dim == t.shape[-1]:
        # full-width table (zero-padded angles rotate by identity): pure
        # elementwise — no slice/concat, so XLA emits no layout copies
        return t * cos + rotate_half(t) * sin
    t_rot, t_pass = t[..., :rot_dim], t[..., rot_dim:]
    t_rot = t_rot * cos + rotate_half(t_rot) * sin
    return jnp.concatenate((t_rot, t_pass), axis=-1)


def dalle_rotary_table(
    dim_head: int,
    text_len: int,
    image_fmap_size: int,
    theta: float = 10000.0,
    max_freq: float = 10.0,
) -> np.ndarray:
    """Precompute the DALL-E rotary angle table.

    ``text_len`` counts the <bos> token (reference text_seq_len + 1); the image
    part has image_fmap_size**2 positions. Output shape is
    (text_len + image_fmap_size**2 - 1, 3 * 2 * (dim_head // 3 // 2)) — the
    trailing position is dropped because the model truncates the final token
    before the transformer (reference transformer.py:221-222).

    Layout along the channel axis, mirroring the reference scheme:
      [0, r)    : 1-D text angles; image positions pinned at position 8192
      [r, 3r)   : 2-D axial pixel angles (row then col); text pinned at -10
    where r = 2 * (dim_head // 3 // 2).
    """
    rot_dim = dim_head // 3
    img_seq_len = image_fmap_size**2

    lf = lang_freqs(rot_dim, theta)
    pf = pixel_freqs(rot_dim, max_freq)

    # 1-D text part.
    text_1d = angles(np.arange(text_len), lf)
    img_1d = angles(np.full((img_seq_len,), 8192.0), lf)
    part_text = np.concatenate((text_1d, img_1d), axis=0)

    # 2-D axial image part over a [-1, 1] pixel grid.
    axial = angles(np.linspace(-1.0, 1.0, image_fmap_size), pf)  # (f, r)
    rows = np.broadcast_to(axial[:, None, :], (image_fmap_size, image_fmap_size, axial.shape[-1]))
    cols = np.broadcast_to(axial[None, :, :], (image_fmap_size, image_fmap_size, axial.shape[-1]))
    img_2d = np.concatenate((rows, cols), axis=-1).reshape(img_seq_len, -1)
    text_2d = np.tile(angles(np.full((text_len,), -10.0), pf), (1, 2))
    part_axial = np.concatenate((text_2d, img_2d), axis=0)

    table = np.concatenate((part_text, part_axial), axis=-1)
    return table[:-1].astype(np.float32)
