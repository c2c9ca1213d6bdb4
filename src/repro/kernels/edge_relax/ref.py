"""Pure-jnp oracle for the edge-relaxation kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp

INF32 = 1 << 29  # plain int: pallas kernels must not capture traced constants


def edge_relax(keys: jax.Array, src: jax.Array, dst: jax.Array,
               valid: jax.Array, step, n: int,
               w: jax.Array | None = None) -> jax.Array:
    """cand[v] = min over valid edges (u,v) of keys[u] + step·w; INF if none.

    The add saturates: keys and step·w are both non-negative, so an int32
    overflow shows up as a negative sum — clamp those to INF32 instead of
    letting a near-INF key pass a heavy edge as a small key.
    """
    sw = step if w is None else step * w
    s = keys[src] + sw
    cand = jnp.minimum(jnp.where(s < 0, INF32, s), INF32)
    cand = jnp.where(valid, cand, INF32)
    out = jax.ops.segment_min(cand, dst, num_segments=n)
    return jnp.minimum(out, INF32)


def frontier_or(words: jax.Array, src: jax.Array, dst: jax.Array,
                valid: jax.Array, n: int, nbits: int = 32) -> jax.Array:
    """out[:, v] = OR of words[:, u] over valid edges (u, v); 0 if none.

    `words` is [W, V] uint32 (a packed bit plane per word). XLA has no
    OR scatter, so each bit reduces by destination on its own (a segment
    max of 0/1) and the bits are packed back. Only the low `nbits` bits
    of each word are reduced (fewer queries than 32 leave the rest 0), so
    a small batch costs no more than one int32 plane per query.
    """
    bits = jnp.arange(nbits, dtype=jnp.uint32)
    got = (words[:, src].T[..., None] >> bits) & 1         # [E, W, nbits]
    got = jnp.where(valid[:, None, None], got, 0).astype(jnp.uint8)
    hit = jax.ops.segment_max(got, dst, num_segments=n)
    return jnp.sum(hit.astype(jnp.uint32) << bits, axis=-1,
                   dtype=jnp.uint32).T
