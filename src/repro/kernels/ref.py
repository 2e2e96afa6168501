"""Pure-jnp oracles for every Pallas kernel (the ``ref.py`` contract).

Each function is the mathematical definition with no blocking/tiling —
tests sweep shapes/dtypes and assert_allclose kernels against these.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def mha_reference(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                  causal: bool = True, window: int = 0,
                  softcap: float = 0.0) -> jnp.ndarray:
    """q [B,H,S,d]; k/v [B,Hkv,S,d]. Dense softmax attention."""
    B, H, S, d = q.shape
    Hkv = k.shape[1]
    G = H // Hkv
    k = jnp.repeat(k, G, axis=1)
    v = jnp.repeat(v, G, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * (d ** -0.5)
    if softcap > 0:
        s = jnp.tanh(s / softcap) * softcap
    qpos = jnp.arange(S)[:, None]
    kpos = jnp.arange(S)[None, :]
    mask = jnp.ones((S, S), bool)
    if causal:
        mask &= kpos <= qpos
    if window > 0:
        mask &= kpos > qpos - window
    s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


def decode_reference(q: jnp.ndarray, k_cache: jnp.ndarray,
                     v_cache: jnp.ndarray, lengths: jnp.ndarray,
                     window: int = 0, softcap: float = 0.0) -> jnp.ndarray:
    """q [B,H,d]; caches [B,Hkv,S,d]; lengths [B] (valid prefix, incl. pos)."""
    B, H, d = q.shape
    Hkv = k_cache.shape[1]
    G = H // Hkv
    k = jnp.repeat(k_cache, G, axis=1)
    v = jnp.repeat(v_cache, G, axis=1)
    s = jnp.einsum("bhd,bhkd->bhk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * (d ** -0.5)
    if softcap > 0:
        s = jnp.tanh(s / softcap) * softcap
    kpos = jnp.arange(k.shape[2])[None, None, :]
    valid = kpos < lengths[:, None, None]
    if window > 0:
        valid &= kpos >= (lengths[:, None, None] - window)
    s = jnp.where(valid, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhk,bhkd->bhd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


def segment_sum_sorted_reference(msg: jnp.ndarray, dst: jnp.ndarray,
                                 n_nodes: int) -> jnp.ndarray:
    """msg [E, D], dst [E] sorted ascending. -> [n_nodes, D]."""
    return jax.ops.segment_sum(msg, dst, num_segments=n_nodes,
                               indices_are_sorted=True)


def embedding_bag_reference(table: jnp.ndarray, ids: jnp.ndarray,
                            mask: jnp.ndarray,
                            combiner: str = "mean") -> jnp.ndarray:
    """table [V, D]; ids/mask [B, F, NNZ] -> [B, F, D]."""
    emb = table[ids] * mask[..., None].astype(table.dtype)
    s = emb.sum(axis=2)
    if combiner == "sum":
        return s
    cnt = jnp.maximum(mask.sum(axis=2), 1.0)[..., None].astype(table.dtype)
    return s / cnt


def triple_scan_reference(cols, s: int, p: int, o: int) -> jnp.ndarray:
    """cols (s, p, o) three [T] int32 columns; s/p/o pattern ids, -1 ==
    wildcard.

    Returns int32 match mask [T]."""
    m = jnp.ones(cols[0].shape[0], bool)
    for col, v in zip(cols, (s, p, o)):
        if v >= 0:
            m &= col == v
    return m.astype(jnp.int32)


def probe_sorted_reference(keys: jnp.ndarray,
                           probes: jnp.ndarray) -> tuple[jnp.ndarray,
                                                         jnp.ndarray]:
    """keys [K] sorted ascending; probes [...]. -> (lo, hi) searchsorted
    left/right bounds, the matcher's ``np.searchsorted`` probe."""
    lo = jnp.searchsorted(keys, probes, side="left").astype(jnp.int32)
    hi = jnp.searchsorted(keys, probes, side="right").astype(jnp.int32)
    return lo, hi


def scan_probe_reference(cols, s: int, p: int, o: int,
                         keys: jnp.ndarray,
                         col: int) -> tuple[jnp.ndarray, jnp.ndarray,
                                            jnp.ndarray]:
    """Fused scan+probe oracle: scan mask plus searchsorted bounds of
    every row's probe-column value (col 0 = subject, 2 = object)."""
    mask = triple_scan_reference(cols, s, p, o)
    lo, hi = probe_sorted_reference(keys, cols[col])
    return mask, lo, hi
