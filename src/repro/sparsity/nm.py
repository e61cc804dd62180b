"""N:M structured sparsity utilities (magnitude pruning + CP packing).

The packed layout matches the paper's STC description (Fig. 14): each
nonzero weight carries an offset-based coordinate-payload (CP) metadata
entry locating it within its block of M values along the contraction
axis.  This is the format the nm_spmm Pallas kernel consumes and the
format model `RankFormat.CP` in the analytical engine describes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def nm_prune_dense(w: jax.Array, n: int = 2, m: int = 4) -> jax.Array:
    """Magnitude-prune W (K, N) to N:M structure along K (axis 0)."""
    K, N = w.shape
    assert K % m == 0, f"K={K} not divisible by m={m}"
    blocks = w.reshape(K // m, m, N)
    mag = jnp.abs(blocks)
    # keep the n largest per block
    thresh = -jnp.sort(-mag, axis=1)[:, n - 1:n, :]
    keep = mag >= thresh
    # break ties deterministically: cap at exactly n kept via cumsum
    order = jnp.argsort(-mag, axis=1)
    rank = jnp.argsort(order, axis=1)
    keep = rank < n
    return (blocks * keep).reshape(K, N)


def pack_nm(w: jax.Array, n: int = 2, m: int = 4):
    """Pack an N:M-sparse W (K, N) -> (values (K//m*n, N), idx (K//m*n, N)).

    idx entries are the offsets within each M-block (CP metadata,
    ceil(log2(m)) bits of information — stored as int8)."""
    K, N = w.shape
    blocks = w.reshape(K // m, m, N)
    nz = blocks != 0
    # order positions: nonzeros first (stable), take first n
    order = jnp.argsort(~nz, axis=1, stable=True)[:, :n, :]   # (K//m, n, N)
    vals = jnp.take_along_axis(blocks, order, axis=1)
    return (vals.reshape(K // m * n, N),
            order.astype(jnp.int8).reshape(K // m * n, N))


def unpack_nm(values: jax.Array, idx: jax.Array, m: int = 4) -> jax.Array:
    """Inverse of pack_nm: (K//m*n, N) -> dense (K, N)."""
    Kn, N = values.shape
    # infer n from idx range? caller supplies m; n = values rows per block
    # derived from the packed layout: each block contributed n rows
    # -> n = Kn / (K/m); K = Kn*m/n. We need n: use max idx? Store-free:
    # caller knows; default n inferred by m and divisibility below.
    raise NotImplementedError("use unpack_nm_with(n=...)")


def offsets_bits(m: int) -> int:
    """CP metadata width for an offset in [0, m)."""
    return max(1, (m - 1).bit_length())


def pack_offsets(idx: jax.Array, m: int) -> jax.Array:
    """Bit-pack int8 offsets (R, N) into uint8 rows: `per = 8 //
    offsets_bits(m)` offsets per byte along the row axis -> (R//per, N).
    This closes the int8-layout gap to the 0.5625x (2:4) weight-traffic
    bound recorded in EXPERIMENTS.md §Perf."""
    bits = offsets_bits(m)
    per = 8 // bits
    R, N = idx.shape
    assert R % per == 0, f"rows {R} not divisible by {per} offsets/byte"
    g = idx.astype(jnp.uint8).reshape(R // per, per, N)
    shifts = (jnp.arange(per, dtype=jnp.uint8) * bits)[None, :, None]
    return (g << shifts).sum(axis=1).astype(jnp.uint8)


def unpack_offsets(packed: jax.Array, m: int, rows: int) -> jax.Array:
    """Inverse of pack_offsets -> int32 (rows, N).  Widens to int32
    before the shifts and the reshape: the TPU kernel compiler (Mosaic)
    cannot reshape uint8 vectors, and the nm_spmm kernel calls this."""
    bits = offsets_bits(m)
    per = 8 // bits
    p = packed.astype(jnp.int32)
    shifts = jax.lax.broadcasted_iota(
        jnp.int32, (p.shape[0], per, p.shape[1]), 1) * bits
    offs = (p[:, None, :] >> shifts) & ((1 << bits) - 1)
    return offs.reshape(rows, p.shape[1])


def unpack_nm_with(values: jax.Array, idx: jax.Array, n: int, m: int
                   ) -> jax.Array:
    Kn, N = values.shape
    G = Kn // n
    vals = values.reshape(G, n, N)
    offs = idx.reshape(G, n, N).astype(jnp.int32)
    onehot = (offs[:, :, None, :] ==
              jnp.arange(m, dtype=jnp.int32)[None, None, :, None])
    dense = (vals[:, :, None, :] * onehot.astype(values.dtype)).sum(axis=1)
    return dense.reshape(G * m, N)
