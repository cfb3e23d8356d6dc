"""Rotary position embeddings (RoPE), rotate-half or adjacent-pair form.

Position information injected by rotating each (q, k) head-dim pair by a
position-dependent angle — no learned position table, exact relative
offsets, and lengths extrapolate beyond training. Applied to q/k BEFORE
the attention dispatch, so every kernel path (XLA, Pallas flash, ring)
gets RoPE for free; under sequence parallelism the caller passes the
shard's global ``positions`` so rotations stay globally consistent.

The rotate-half (GPT-NeoX / LLaMA) convention: the head dim is split in
halves (x1, x2) and rotated as (x1·cos − x2·sin, x2·cos + x1·sin) with
frequencies theta^(−2i/d). ``interleaved=True`` rotates ADJACENT pairs
(x[2i], x[2i+1]) by the same angles instead (the GPT-J / DeepSeek
``rope_interleave`` convention) and leaves each pair where it was.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def rope(
    x: jax.Array,
    positions: Optional[jax.Array] = None,
    theta: float = 10000.0,
    interleaved: bool = False,
    seq_axis: int = 1,
) -> jax.Array:
    """Rotate (B, S, N, H) queries or keys by their positions; with
    ``seq_axis=2`` the head-major (B, N, S, H) layout the flash kernel
    takes.

    ``positions``: (S,) int32 global positions shared across the batch, or
    (B, S) per-row positions (paged decode: each slot sits at its own
    offset); default arange(S). Angles are computed in float32 regardless
    of the compute dtype.
    """
    head_dim = x.shape[-1]
    if head_dim % 2:
        raise ValueError(f"RoPE needs an even head_dim, got {head_dim}")
    if seq_axis not in (1, 2):
        raise ValueError(
            f"seq_axis is 1 (B, S, N, H) or 2 (B, N, S, H), got {seq_axis}"
        )
    half = head_dim // 2
    if positions is None:
        positions = jnp.arange(x.shape[seq_axis])
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)

    def along_seq(a):
        """(S, half) or per-row (B, S, half) angles laid along x's
        sequence axis."""
        if a.ndim == 3:
            return a[:, :, None, :] if seq_axis == 1 else a[:, None, :, :]
        return a[None, :, None, :] if seq_axis == 1 else a[None, None, :, :]

    if interleaved:
        if positions.ndim != 1:
            raise ValueError("interleaved RoPE takes (S,) positions")
        angles = positions.astype(jnp.float32)[:, None] * freqs  # (S, half)
        cos = along_seq(jnp.cos(angles))
        sin = along_seq(jnp.sin(angles))
        pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], half, 2)
        x1, x2 = pairs[..., 0], pairs[..., 1]
        rotated = jnp.stack(
            [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
        )
        return rotated.reshape(x.shape).astype(x.dtype)
    if positions.ndim == 2:  # (B, S): per-row offsets
        angles = positions.astype(jnp.float32)[..., None] * freqs
        cos = along_seq(jnp.cos(angles))
        sin = along_seq(jnp.sin(angles))
        x1 = x[..., :half].astype(jnp.float32)
        x2 = x[..., half:].astype(jnp.float32)
        rotated = jnp.concatenate(
            [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
        )
        return rotated.astype(x.dtype)
    angles = positions.astype(jnp.float32)[:, None] * freqs  # (S, half)
    cos = along_seq(jnp.cos(angles))
    sin = along_seq(jnp.sin(angles))
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    rotated = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return rotated.astype(x.dtype)
