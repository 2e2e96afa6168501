"""Triple-pattern scan (Pallas TPU) — the paper's candidate-scan hot spot.

BGP matching begins with a scan over dictionary-encoded triples applying the
constant components of a pattern (see ``sparql.matcher._candidates``). On
the edge servers this touches every stored triple per query; on TPU we
stream ``(bt,)`` blocks of three lane-dense int32 columns (s, p, o) HBM ->
VMEM and evaluate the constant/wildcard mask on the VPU, emitting an int32
match mask (compaction stays in XLA: cumsum + take, which is already
optimal there).

The columns are separate 1-D arrays, not one ``[T, 3]`` table: a block of a
``[T, 3]`` array has 3 lanes out of 128, so XLA re-lays the whole table out
with padded lanes before the kernel (512 B of HBM temp per triple); three
1-D columns stream at 12 B per triple with no copy.

The pattern (s, p, o) arrives as scalar prefetch (-1 == wildcard), so ONE
compiled kernel serves every pattern — no recompilation per query, which is
what a serving system needs. Rows whose predicate id is negative are
padding (see :func:`pad_columns`) and match no pattern, wildcards included.

The scan is the FIRST stage of the device-resident join pipeline
(:mod:`repro.sparql.device_join`): its mask is compacted on device and fed
straight into the ``probe_sorted`` join kernel — or the scan and first
probe fuse into one launch via :func:`repro.kernels.join_probe.scan_probe`
— so eligible queries never round-trip to the host between leaf scan and
join.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: :func:`triple_scan_many` evaluates patterns in groups of at most this
#: many, one ``(group, bt)`` output tile per group (the int32 sublane tiling)
PATTERN_GROUP = 8

Columns = tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]


def pad_columns(cols: Columns, length: int) -> Columns:
    """Pad (s, p, o) columns to ``length`` rows with never-matching rows."""
    n = cols[0].shape[0]
    if n == length:
        return tuple(c.astype(jnp.int32) for c in cols)
    return tuple(jnp.pad(c.astype(jnp.int32), (0, length - n),
                         constant_values=-1) for c in cols)


def match_rows(s_col, p_col, o_col, s, p, o):
    """Bool match of column values against one pattern (-1 == wildcard);
    padding rows (negative predicate) never match."""
    m = p_col >= 0
    m &= (s_col == s) | (s < 0)
    m &= (p_col == p) | (p < 0)
    m &= (o_col == o) | (o < 0)
    return m


def _scan_kernel(pat_ref, s_ref, p_ref, o_ref, mask_ref):
    m = match_rows(s_ref[...], p_ref[...], o_ref[...],
                   pat_ref[0], pat_ref[1], pat_ref[2])
    mask_ref[...] = m.astype(jnp.int32)


def whole_tiles(**blocks: int) -> None:
    """Refuse 1-D block sizes that are not whole 1024-element tiles — the
    TPU tiling of a 1-D int32 array, which the compiler enforces."""
    for name, n in blocks.items():
        if n % 1024:
            raise ValueError(f"{name} must be a multiple of 1024, got {n}")


@functools.partial(jax.jit, static_argnames=("bt", "interpret"))
def triple_scan(cols: Columns, pattern: jnp.ndarray, bt: int = 2048,
                interpret: bool = False) -> jnp.ndarray:
    """cols (s, p, o) three [T] int32 columns; pattern [3] int32 with -1
    wildcards.

    Returns int32 match mask [T].
    """
    whole_tiles(bt=bt)
    T = cols[0].shape[0]
    t_pad = max(bt, pl.cdiv(T, bt) * bt)
    col_spec = pl.BlockSpec((bt,), lambda i, pat: (i,))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(t_pad // bt,),
        in_specs=[col_spec] * 3,
        out_specs=col_spec,
    )
    mask = pl.pallas_call(
        _scan_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t_pad,), jnp.int32),
        interpret=interpret,
    )(pattern.astype(jnp.int32), *pad_columns(cols, t_pad))
    return mask[:T]


def _scan_many_kernel(pat_ref, s_ref, p_ref, o_ref, mask_ref, *,
                      group: int):
    g = pl.program_id(0) * group
    s_col, p_col, o_col = s_ref[...], p_ref[...], o_ref[...]
    for j in range(group):
        m = match_rows(s_col, p_col, o_col, pat_ref[g + j, 0],
                       pat_ref[g + j, 1], pat_ref[g + j, 2])
        mask_ref[j, :] = m.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("bt", "interpret"))
def triple_scan_many(cols: Columns, patterns: jnp.ndarray,
                     bt: int = 2048, interpret: bool = False) -> jnp.ndarray:
    """Batched scan: cols (s, p, o) [T] each, patterns [Q, 3] (-1
    wildcards) -> int32 masks [Q, T].

    Grid (Q/8, T/bt): each step evaluates a group of 8 patterns (all of
    them when Q <= 8) against one block of every column and writes one
    ``(group, bt)`` tile, so one compiled kernel evaluates *all
    deduplicated scans of a query batch* in a single launch — the
    batch-fusion counterpart of :func:`triple_scan` that
    ``sparql.engine``'s JAX backend uses to pre-populate its scan memo.
    Past 8, ``Q`` is padded to a multiple of 8 with wildcard rows that are
    sliced away.
    """
    whole_tiles(bt=bt)
    T = cols[0].shape[0]
    Q = patterns.shape[0]
    t_pad = max(bt, pl.cdiv(T, bt) * bt)
    group = min(Q, PATTERN_GROUP)
    q_pad = pl.cdiv(Q, group) * group
    pats = jnp.pad(patterns.astype(jnp.int32), ((0, q_pad - Q), (0, 0)),
                   constant_values=-1)
    col_spec = pl.BlockSpec((bt,), lambda qi, i, pat: (i,))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(q_pad // group, t_pad // bt),
        in_specs=[col_spec] * 3,
        out_specs=pl.BlockSpec((group, bt), lambda qi, i, pat: (qi, i)),
    )
    mask = pl.pallas_call(
        functools.partial(_scan_many_kernel, group=group),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((q_pad, t_pad), jnp.int32),
        interpret=interpret,
    )(pats, *pad_columns(cols, t_pad))
    return mask[:Q, :T]
