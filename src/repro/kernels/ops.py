"""Dispatching wrappers: one API, three backends (pallas / interpret / xla).

Models call these; on TPU the Pallas kernels run compiled, on CPU they run
via interpret mode (tests) or fall back to the jnp reference (production
CPU path — interpret mode is a correctness tool, not a fast path).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import ref
from .decode_attention import decode_attention as _decode_pallas
from .embedding_bag import embedding_bag_pallas
from .flash_attention import flash_attention as _flash_pallas
from .segment_mp import segment_sum_sorted as _segmp_pallas


def _backend(impl: str) -> str:
    # NOTE: this picks pallas vs the jnp *reference* (a different axis than
    # repro.kernels.default_interpret, which resolves pallas_call's
    # interpret flag); the TPU-grid kernels only compile on TPU, so GPU
    # uses the XLA reference here.
    if impl != "auto":
        return impl
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def attention(q, k, v, *, causal=True, window=0, softcap=0.0,
              impl: str = "auto"):
    b = _backend(impl)
    if b == "xla":
        return ref.mha_reference(q, k, v, causal=causal, window=window,
                                 softcap=softcap)
    return _flash_pallas(q, k, v, causal=causal, window=window,
                         softcap=softcap, interpret=(b == "interpret"))


def decode_attention(q, k_cache, v_cache, lengths, *, window=0, softcap=0.0,
                     impl: str = "auto"):
    b = _backend(impl)
    if b == "xla":
        return ref.decode_reference(q, k_cache, v_cache, lengths,
                                    window=window, softcap=softcap)
    return _decode_pallas(q, k_cache, v_cache, lengths, window=window,
                          softcap=softcap, interpret=(b == "interpret"))


def segment_sum_sorted(msg, dst, n_nodes: int, *, impl: str = "auto"):
    b = _backend(impl)
    if b == "xla":
        return ref.segment_sum_sorted_reference(msg, dst, n_nodes)
    return _segmp_pallas(msg, dst, n_nodes, interpret=(b == "interpret"))


def embedding_bag(table, ids, mask, *, combiner="mean", impl: str = "auto"):
    b = _backend(impl)
    if b == "xla":
        return ref.embedding_bag_reference(table, ids, mask,
                                           combiner=combiner)
    return embedding_bag_pallas(table, ids, mask, combiner=combiner,
                                interpret=(b == "interpret"))

