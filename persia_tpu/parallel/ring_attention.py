"""Ring attention: context parallelism for long sequences.

The reference has no long-context machinery (SURVEY.md §5 — its
"sequences" are bags of IDs), but sequence towers over long user
histories are a first-class need here. This implements blockwise ring
attention (Liu et al.'s ring attention formulation): the sequence axis is
sharded over a mesh axis; each step combines the local query block with
the currently-held K/V block using the online-softmax (flash) update,
then rotates K/V around the ring with ``lax.ppermute`` — compute on the
current block overlaps the ICI transfer of the next, and no shard ever
materializes the full sequence.

Every kernel takes an optional ``kv_mask`` (B, T_k) marking valid key
positions — masking happens at SCORE level (-inf before softmax), the
only correct place (zeroing/poisoning key vectors changes scores by
q·k_poison, which can be arbitrarily positive). Fully-masked query rows
produce zero output.

Use inside ``shard_map`` (see :func:`ring_self_attention`), or directly
under ``jit`` on one device where it degenerates to single-block flash
attention.
"""

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P


def _shard_map(f, mesh, in_specs, out_specs):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def reference_attention(q, k, v, causal: bool = False, kv_mask=None):
    """O(T^2)-memory reference: softmax(q kᵀ / sqrt(d)) v.

    q, k, v: (B, H, T, Dh); kv_mask: optional (B, T_k) bool of valid key
    positions (scores of invalid keys are -inf; fully-masked query rows
    yield 0)."""
    scale = 1.0 / jnp.sqrt(q.shape[-1]).astype(q.dtype)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        # position i attends to keys <= i; with t_q != t_k the mask is
        # the rectangular slice of the square relation, not tril of a
        # (t_q, t_q) matrix
        q_pos = jnp.arange(q.shape[2])[:, None]
        k_pos = jnp.arange(k.shape[2])[None, :]
        s = jnp.where((q_pos >= k_pos)[None, None], s, -jnp.inf)
    if kv_mask is not None:
        s = jnp.where(kv_mask[:, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    if kv_mask is not None:
        p = jnp.where(jnp.isnan(p), 0.0, p)  # fully-masked rows -> 0
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def _flash_update(o, m, l, s, v_blk):
    """One online-softmax accumulation over a score block ``s`` that is
    already -inf-masked; numerically guards rows with no visible keys
    yet (m stays -inf until the first finite score). Shared by the ring
    scan and the local chunked scan so the delicate guard logic cannot
    diverge between strategies."""
    m_new = jnp.maximum(m, s.max(axis=-1))
    safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(s - safe_m[..., None])
    p = jnp.where(jnp.isfinite(s), p, 0.0)
    correction = jnp.where(jnp.isfinite(m), jnp.exp(m - safe_m), 0.0)
    l = l * correction + p.sum(axis=-1)
    o = o * correction[..., None] + jnp.einsum(
        "bhqk,bhkd->bhqd", p, v_blk.astype(jnp.float32))
    return o, m_new, l


def ring_attention(q, k, v, axis_name: Optional[str] = None,
                   causal: bool = False, kv_mask=None):
    """Blockwise attention over a ring-sharded sequence axis.

    q, k, v: (B, H, T_local, Dh) — this shard's sequence block; kv_mask:
    optional (B, T_local) bool for this shard's keys (rotates around the
    ring with K/V). With ``axis_name=None`` (or axis size 1) this is
    plain flash attention on the local block.
    """
    if axis_name is not None:
        axis_size = lax.psum(1, axis_name)
        my_idx = lax.axis_index(axis_name)
    else:
        axis_size = 1
        my_idx = 0
    b, h, t_q, dh = q.shape
    t_k = k.shape[2]
    scale = 1.0 / jnp.sqrt(jnp.asarray(dh, jnp.float32))
    q32 = q.astype(jnp.float32)
    if kv_mask is None:
        kv_mask = jnp.ones((b, t_k), bool)

    q_pos = my_idx * t_q + lax.iota(jnp.int32, t_q)  # global query positions

    def step(carry, i):
        o, m, l, k_blk, v_blk, m_blk = carry
        # the block currently held originated on shard (my_idx - i) % size
        src = (my_idx - i) % axis_size
        s = jnp.einsum("bhqd,bhkd->bhqk", q32,
                       k_blk.astype(jnp.float32)) * scale
        if causal:
            k_pos = src * t_k + lax.iota(jnp.int32, t_k)
            mask = q_pos[:, None] >= k_pos[None, :]
            s = jnp.where(mask[None, None], s, -jnp.inf)
        s = jnp.where(m_blk[:, None, None, :], s, -jnp.inf)
        o, m, l = _flash_update(o, m, l, s, v_blk)
        if axis_name is not None and axis_size > 1:
            perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]
            k_blk = lax.ppermute(k_blk, axis_name, perm)
            v_blk = lax.ppermute(v_blk, axis_name, perm)
            m_blk = lax.ppermute(m_blk, axis_name, perm)
        return (o, m, l, k_blk, v_blk, m_blk), None

    o0 = jnp.zeros((b, h, t_q, dh), jnp.float32)
    m0 = jnp.full((b, h, t_q), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h, t_q), jnp.float32)
    (o, m, l, _, _, _), _ = lax.scan(
        step, (o0, m0, l0, k, v, kv_mask), jnp.arange(axis_size))
    l = jnp.maximum(l, 1e-20)
    return (o / l[..., None]).astype(q.dtype)


def local_flash_attention(q, k, v, causal: bool = False,
                          chunk_size: int = 512, kv_mask=None):
    """Single-device blockwise (flash) attention: O(T·chunk) score memory.

    q, k, v: (B, H, T, Dh); kv_mask optional (B, T_k). K/V stream
    through in ``chunk_size`` blocks with the same online-softmax update
    :func:`ring_attention` uses across shards — the inner kernel for
    strategies that hold the full sequence per device (Ulysses) without
    materializing the (T, T) score matrix."""
    b, h, t_q, dh = q.shape
    t_k = k.shape[2]
    if t_k <= chunk_size:
        return ring_attention(q, k, v, axis_name=None, causal=causal,
                              kv_mask=kv_mask)
    if kv_mask is None:
        kv_mask = jnp.ones((b, t_k), bool)
    n_chunks = -(-t_k // chunk_size)
    pad = n_chunks * chunk_size - t_k
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        kv_mask = jnp.pad(kv_mask, ((0, 0), (0, pad)))  # padding invalid
    k_chunks = k.reshape(b, h, n_chunks, chunk_size, dh)
    v_chunks = v.reshape(b, h, n_chunks, chunk_size, dh)
    m_chunks = kv_mask.reshape(b, n_chunks, chunk_size)
    scale = 1.0 / jnp.sqrt(jnp.asarray(dh, jnp.float32))
    q32 = q.astype(jnp.float32)
    q_pos = lax.iota(jnp.int32, t_q)

    def step(carry, blk):
        o, m, l = carry
        k_blk, v_blk, m_blk, ci = blk
        s = jnp.einsum("bhqd,bhkd->bhqk", q32,
                       k_blk.astype(jnp.float32)) * scale
        if causal:
            k_pos = ci * chunk_size + lax.iota(jnp.int32, chunk_size)
            cmask = q_pos[:, None] >= k_pos[None, :]
            s = jnp.where(cmask[None, None], s, -jnp.inf)
        s = jnp.where(m_blk[:, None, None, :], s, -jnp.inf)
        o, m, l = _flash_update(o, m, l, s, v_blk)
        return (o, m, l), None

    o0 = jnp.zeros((b, h, t_q, dh), jnp.float32)
    m0 = jnp.full((b, h, t_q), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h, t_q), jnp.float32)
    (o, m, l), _ = lax.scan(
        step, (o0, m0, l0),
        (k_chunks.transpose(2, 0, 1, 3, 4),
         v_chunks.transpose(2, 0, 1, 3, 4),
         m_chunks.transpose(1, 0, 2),
         jnp.arange(n_chunks)),
    )
    l = jnp.maximum(l, 1e-20)
    return (o / l[..., None]).astype(q.dtype)


def seq_sharded(inner, mesh: Mesh, seq_axis: str):
    """Shared shard_map wrapper for context-parallel attention:
    ``inner(q_local, k_local, v_local, kv_mask_local)`` runs per shard;
    q/k/v (B, H, T, Dh) and kv_mask (B, T) shard T over ``seq_axis``;
    output keeps the q/k/v sharding."""
    spec = P(None, None, seq_axis, None)
    mspec = P(None, seq_axis)
    return _shard_map(inner, mesh, (spec, spec, spec, mspec), spec)


def ring_self_attention(q, k, v, mesh: Mesh, seq_axis: str = "model",
                        causal: bool = False, kv_mask=None):
    """shard_map wrapper: q/k/v (B, H, T, Dh) with T sharded on
    ``seq_axis``; returns attention output with the same sharding."""
    if kv_mask is None:
        kv_mask = jnp.ones((q.shape[0], k.shape[2]), bool)

    def inner(q, k, v, m):
        return ring_attention(q, k, v, axis_name=seq_axis, causal=causal,
                              kv_mask=m)

    return seq_sharded(inner, mesh, seq_axis)(q, k, v, kv_mask)
