"""Tropical (min-plus) contraction kernel for Eq.-3 query upper bounds.

    out[b] = min_{i,j}  S[b,i] + H[i,j] + T[b,j]

This is the per-query hot path of the serving engine: for a query batch of
B pairs against R landmarks it does B·R² int32 add+min ops. On TPU the VPU
(8×128 lanes) executes the adds/mins; the landmark axes are padded to the
128-lane register width and the batch axis is tiled into VMEM blocks, so the
working set per grid step is  BB·RP·4 · 2 (S,T) + RP²·4 (H) + BB·RP·4 (acc)
≈ 0.4 MB for BB=256, RP=128 — far under the ~16 MB VMEM budget, leaving the
pipeline free to double-buffer blocks while the VPU runs.

H may be rectangular [P, R] with S [B, P]: that is the shard-local
contraction of `core/shard.py`'s model-sharded query bound — each shard
contracts its own P = R/M highway rows against the all-gathered target
labels and a `pmin` over the mesh finishes the reduction. P = R recovers
the full (unsharded) bound. INF padding is the min-plus identity, so the
padded contraction is exact.

The inner contraction loops over the P real rows of H instead of
materialising the [BB, PP, RP] cube (which would blow VMEM at 8 MB+ per
block).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

INF32 = 1 << 29  # plain int: pallas kernels must not capture traced constants

DEFAULT_BB = 256   # query-batch tile
LANES = 128        # TPU vector lane width; landmark axis padded to this


def _minplus_kernel(s_ref, h_ref, t_ref, o_ref, *, p: int):
    s = s_ref[...]          # [BB, PP] int32
    h = h_ref[...]          # [PP, RP]
    acc = jnp.full((s.shape[0], h.shape[1]), INF32, jnp.int32)
    # acc[b, j] = min over i of s[b, i] + h[i, j]. The loop is unrolled
    # over the p real rows with static slices (Mosaic lowers no dynamic
    # lane slice); the INF padding rows past p are min-plus identities.
    for i in range(p):
        acc = jnp.minimum(acc, jnp.minimum(s[:, i:i + 1] + h[i:i + 1, :],
                                           INF32))
    o_ref[...] = jnp.min(jnp.minimum(acc + t_ref[...], INF32), axis=1,
                         keepdims=True)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def minplus_pallas(s: jax.Array, h: jax.Array, t: jax.Array,
                   block_b: int = DEFAULT_BB,
                   interpret: bool = True) -> jax.Array:
    """S [B,P], H [P,R], T [B,R] int32 → out [B] int32.

    P = R is the full Eq.-3 bound; P < R is a shard-local partial bound
    (finished by a `pmin` across shards). Pads P and R→multiples of 128
    lanes (INF padding is the min-plus identity) and B→multiple of block_b.
    """
    b, p = s.shape
    p2, r = h.shape
    if p2 != p or t.shape != (b, r):
        raise ValueError(f"shape mismatch: S {s.shape}, H {h.shape}, "
                         f"T {t.shape}")
    pp = max(LANES, -(-p // LANES) * LANES)
    rp = max(LANES, -(-r // LANES) * LANES)
    bp = -(-b // block_b) * block_b

    pad_s = jnp.full((bp, pp), INF32, jnp.int32).at[:b, :p].set(s)
    pad_t = jnp.full((bp, rp), INF32, jnp.int32).at[:b, :r].set(t)
    pad_h = jnp.full((pp, rp), INF32, jnp.int32).at[:p, :r].set(h)

    out = pl.pallas_call(
        functools.partial(_minplus_kernel, p=p),
        grid=(bp // block_b,),
        in_specs=[
            pl.BlockSpec((block_b, pp), lambda i: (i, 0)),
            pl.BlockSpec((pp, rp), lambda i: (0, 0)),
            pl.BlockSpec((block_b, rp), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((bp, 1), jnp.int32),
        interpret=interpret,
    )(pad_s, pad_h, pad_t)
    return out[:b, 0]
