"""Sorted-probe join (Pallas TPU) — the device side of the matcher's join.

``sparql.matcher`` joins a new pattern into the current binding table by
probing each bound value into a *sorted* key column of the candidate set
(``np.searchsorted`` left/right -> ``[lo, hi)`` run bounds over the
``pred_index`` views built in PR 3). This module is the device analogue:

* :func:`probe_sorted` / :func:`probe_sorted_many` — binary search as a
  branchless compare-and-count streaming reduction.  For every probe value
  ``v``: ``lo = sum(keys < v)`` and ``hi = sum(keys <= v)``, accumulated
  block-by-block while the sorted key column streams HBM -> VMEM.  On the
  VPU this beats a gather-based bisection (vector gathers are the weak
  spot; dense compares are free), and the result is *bit-identical* to
  ``np.searchsorted(keys, v, "left"/"right")``.
* :func:`scan_probe` — the fused scan->join kernel for the common
  bound-predicate star shape: one launch computes the candidate-scan mask
  AND the first join's ``[lo, hi)`` bounds from the matched rows' subject
  or object column, with no intermediate materialization between scan and
  probe.

Everything takes the true (unpadded) lengths via scalar prefetch, so key /
probe padding values never affect the counts and ONE compiled kernel
serves every (pattern, key-column) pair — no recompilation per query.
Expansion of the ``[lo, hi)`` runs into binding rows stays in XLA
(cumsum + repeat + gather), mirroring how the scan kernel leaves
compaction to XLA.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .triple_scan import (PATTERN_GROUP, Columns, match_rows, pad_columns,
                          whole_tiles)

_PAD_KEY = jnp.iinfo(jnp.int32).max   # sorted-column padding (ignored via K)


def _probe_many_kernel(meta_ref, key_ref, probe_ref, lo_ref, hi_ref, *,
                       bk: int, bp: int, group: int):
    ki = pl.program_id(2)
    n_keys = meta_ref[0]
    kv = key_ref[...][:, None]                         # [bk, 1] int32
    # index-mask the key padding: only positions < n_keys count
    idx = jax.lax.broadcasted_iota(jnp.int32, (bk, bp), 0) + ki * bk
    valid = idx < n_keys                               # [bk, bp]

    @pl.when(ki == 0)
    def _init():
        lo_ref[...] = jnp.zeros_like(lo_ref)
        hi_ref[...] = jnp.zeros_like(hi_ref)

    for j in range(group):
        v = probe_ref[pl.ds(j, 1), :]                  # [1, bp] int32
        lo_ref[pl.ds(j, 1), :] += ((kv < v) & valid).astype(
            jnp.int32).sum(axis=0, keepdims=True)
        hi_ref[pl.ds(j, 1), :] += ((kv <= v) & valid).astype(
            jnp.int32).sum(axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("bk", "bp", "interpret"))
def probe_sorted_many(keys: jnp.ndarray, probes: jnp.ndarray,
                      bk: int = 2048, bp: int = 512,
                      interpret: bool = False) -> tuple[jnp.ndarray,
                                                        jnp.ndarray]:
    """Batched sorted probe: keys [K] ascending, probes [Q, P] -> lo/hi [Q, P].

    ``lo[q, j] == np.searchsorted(keys, probes[q, j], "left")`` and
    ``hi[q, j]`` the ``"right"`` bound; ``hi - lo`` is each probe's run
    length.  Key padding is masked by true length (any probe value is
    safe); pad *probes* with ``-1`` to get ``lo == hi == 0`` runs for
    non-negative id spaces.  Probe rows go through the kernel in groups of
    up to 8 (one ``(group, bp)`` tile per step), so any Q is legal.
    """
    whole_tiles(bk=bk)
    if bp % 128:
        raise ValueError(f"bp must be a multiple of 128, got {bp}")
    K = keys.shape[0]
    Q, P = probes.shape
    k_pad = max(bk, ((K + bk - 1) // bk) * bk)
    if k_pad != K:
        keys = jnp.pad(keys, (0, k_pad - K), constant_values=_PAD_KEY)
    group = min(Q, PATTERN_GROUP)
    q_pad = pl.cdiv(Q, group) * group
    p_pad = ((P + bp - 1) // bp) * bp
    if (q_pad, p_pad) != (Q, P):
        probes = jnp.pad(probes, ((0, q_pad - Q), (0, p_pad - P)),
                         constant_values=-1)
    meta = jnp.asarray([K], jnp.int32)
    row_spec = pl.BlockSpec((group, bp), lambda qi, pi, ki, meta: (qi, pi))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(q_pad // group, p_pad // bp, k_pad // bk),
        in_specs=[pl.BlockSpec((bk,), lambda qi, pi, ki, meta: (ki,)),
                  row_spec],
        out_specs=[row_spec, row_spec],
    )
    lo, hi = pl.pallas_call(
        functools.partial(_probe_many_kernel, bk=bk, bp=bp, group=group),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((q_pad, p_pad), jnp.int32)] * 2,
        interpret=interpret,
    )(meta, keys.astype(jnp.int32), probes.astype(jnp.int32))
    return lo[:Q, :P], hi[:Q, :P]


def probe_sorted(keys: jnp.ndarray, probes: jnp.ndarray, bk: int = 2048,
                 bp: int = 512,
                 interpret: bool = False) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Sorted probe: keys [K] ascending, probes [P] -> (lo [P], hi [P])."""
    lo, hi = probe_sorted_many(keys, probes[None, :], bk=bk, bp=bp,
                               interpret=interpret)
    return lo[0], hi[0]


def _scan_probe_kernel(meta_ref, key_ref, s_ref, p_ref, o_ref, mask_ref,
                       lo_ref, hi_ref, *, bt: int, bk: int, col: int):
    ki = pl.program_id(1)
    n_keys = meta_ref[3]
    keys = key_ref[...]                                # [bk] int32

    vals = (s_ref if col == 0 else o_ref)[...]         # probe column [bt]
    idx = jax.lax.broadcasted_iota(jnp.int32, (bt, bk), 1) + ki * bk
    valid = idx < n_keys                               # [bt, bk]
    kv = keys[None, :]                                 # [1, bk]
    lo_blk = ((kv < vals[:, None]) & valid).astype(jnp.int32).sum(axis=1)
    hi_blk = ((kv <= vals[:, None]) & valid).astype(jnp.int32).sum(axis=1)

    @pl.when(ki == 0)
    def _init():
        m = match_rows(s_ref[...], p_ref[...], o_ref[...],
                       meta_ref[0], meta_ref[1], meta_ref[2])
        mask_ref[...] = m.astype(jnp.int32)
        lo_ref[...] = lo_blk
        hi_ref[...] = hi_blk

    @pl.when(ki > 0)
    def _acc():
        lo_ref[...] += lo_blk
        hi_ref[...] += hi_blk


@functools.partial(jax.jit,
                   static_argnames=("col", "bt", "bk", "interpret"))
def scan_probe(cols: Columns, pattern: jnp.ndarray,
               keys: jnp.ndarray, col: int, bt: int = 2048, bk: int = 2048,
               interpret: bool = False) -> tuple[jnp.ndarray, jnp.ndarray,
                                                 jnp.ndarray]:
    """Fused candidate scan + first-join probe in one kernel launch.

    cols (s, p, o) three [T] int32 columns; pattern [3] int32 (-1
    wildcards); keys [K] int32 ascending sorted; ``col`` selects the probe
    column (0 = subject, 2 = object).  Returns ``(mask [T], lo [T], hi
    [T])`` where ``mask`` is the scan match mask and ``lo/hi`` are
    searchsorted left/right bounds of *every* row's probe-column value
    (consumers take ``counts = where(mask, hi - lo, 0)``) — the star-shape
    seed scan and its first equi-join without materializing the matched
    rows in between.
    """
    if col not in (0, 2):
        raise ValueError(f"col must be 0 (subject) or 2 (object), got {col}")
    whole_tiles(bt=bt, bk=bk)
    T = cols[0].shape[0]
    K = keys.shape[0]
    t_pad = max(bt, pl.cdiv(T, bt) * bt)
    k_pad = max(bk, ((K + bk - 1) // bk) * bk)
    if k_pad != K:
        keys = jnp.pad(keys, (0, k_pad - K), constant_values=_PAD_KEY)
    meta = jnp.concatenate([pattern.astype(jnp.int32),
                            jnp.asarray([K], jnp.int32)])
    col_spec = pl.BlockSpec((bt,), lambda ti, ki, meta: (ti,))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(t_pad // bt, k_pad // bk),
        in_specs=[pl.BlockSpec((bk,), lambda ti, ki, meta: (ki,))]
        + [col_spec] * 3,
        out_specs=[col_spec] * 3,
    )
    mask, lo, hi = pl.pallas_call(
        functools.partial(_scan_probe_kernel, bt=bt, bk=bk, col=col),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((t_pad,), jnp.int32)] * 3,
        interpret=interpret,
    )(meta, keys.astype(jnp.int32), *pad_columns(cols, t_pad))
    return mask[:T], lo[:T], hi[:T]
