"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret mode."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention
from repro.kernels.embedding_bag import embedding_bag_pallas
from repro.kernels.flash_attention import flash_attention
from repro.kernels.join_probe import (probe_sorted, probe_sorted_many,
                                      scan_probe)
from repro.kernels.segment_mp import segment_sum_sorted
from repro.kernels.triple_scan import triple_scan, triple_scan_many

KEY = jax.random.PRNGKey(0)


def tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


# -- flash attention -----------------------------------------------------------

FLASH_CASES = [
    # B, H, Hkv, S, d, window, softcap
    (2, 4, 2, 128, 32, 0, 0.0),
    (1, 4, 4, 256, 64, 0, 50.0),
    (2, 8, 2, 256, 32, 64, 0.0),
    (1, 2, 1, 64, 16, 32, 30.0),
    (1, 8, 8, 512, 64, 128, 0.0),
]


@pytest.mark.parametrize("case", FLASH_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_vs_ref(case, dtype):
    B, H, Hkv, S, d, win, cap = case
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, H, S, d), dtype)
    k = jax.random.normal(ks[1], (B, Hkv, S, d), dtype)
    v = jax.random.normal(ks[2], (B, Hkv, S, d), dtype)
    out = flash_attention(q, k, v, window=win, softcap=cap, bq=64, bk=64,
                          interpret=True)
    want = ref.mha_reference(q, k, v, window=win, softcap=cap)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **tol(dtype))


def test_flash_attention_block_shape_sweep():
    B, H, S, d = 1, 2, 256, 32
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, H, S, d), jnp.float32)
    k = jax.random.normal(ks[1], (B, H, S, d), jnp.float32)
    v = jax.random.normal(ks[2], (B, H, S, d), jnp.float32)
    want = ref.mha_reference(q, k, v)
    for bq, bk in [(32, 32), (64, 128), (128, 64), (256, 256)]:
        out = flash_attention(q, k, v, bq=bq, bk=bk, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


# -- decode attention ----------------------------------------------------------

DECODE_CASES = [
    # B, H, Hkv, S, d, window
    (2, 4, 2, 256, 32, 0),
    (1, 8, 1, 512, 64, 0),
    (3, 4, 4, 128, 32, 48),
]


@pytest.mark.parametrize("case", DECODE_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_vs_ref(case, dtype):
    B, H, Hkv, S, d, win = case
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (B, H, d), dtype)
    kc = jax.random.normal(ks[1], (B, Hkv, S, d), dtype)
    vc = jax.random.normal(ks[2], (B, Hkv, S, d), dtype)
    lengths = jax.random.randint(ks[3], (B,), 1, S + 1)
    out = decode_attention(q, kc, vc, lengths, window=win, bk=64,
                           interpret=True)
    want = ref.decode_reference(q, kc, vc, lengths, window=win)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **tol(dtype))


# -- GNN segment message passing --------------------------------------------------

@pytest.mark.parametrize("E,N,D", [(100, 40, 16), (1000, 64, 32),
                                   (257, 130, 8), (64, 256, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_segment_mp_vs_ref(E, N, D, dtype):
    ks = jax.random.split(KEY, 2)
    msg = jax.random.normal(ks[0], (E, D), dtype)
    dst = jnp.sort(jax.random.randint(ks[1], (E,), 0, N))
    out = segment_sum_sorted(msg, dst, N, bn=32, bc=64, interpret=True)
    # oracle in fp32: the kernel accumulates in fp32 scratch regardless of
    # input dtype (more accurate than a bf16 pairwise segment_sum)
    want = ref.segment_sum_sorted_reference(msg.astype(jnp.float32), dst, N)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               **tol(dtype))


def test_segment_mp_empty_and_hot_nodes():
    # one node receives everything; most receive nothing
    E, N, D = 512, 64, 16
    msg = jnp.ones((E, D), jnp.float32)
    dst = jnp.zeros((E,), jnp.int32).at[256:].set(63)
    dst = jnp.sort(dst)
    out = segment_sum_sorted(msg, dst, N, bn=16, bc=128, interpret=True)
    assert float(out[0, 0]) == 256.0
    assert float(out[63, 0]) == 256.0
    assert float(jnp.abs(out[1:63]).max()) == 0.0


# -- embedding bag ------------------------------------------------------------------

@pytest.mark.parametrize("B,F,NNZ,V,D", [(4, 3, 4, 100, 16),
                                         (2, 8, 2, 1000, 32),
                                         (8, 1, 6, 50, 64)])
@pytest.mark.parametrize("combiner", ["mean", "sum"])
def test_embedding_bag_vs_ref(B, F, NNZ, V, D, combiner):
    ks = jax.random.split(KEY, 3)
    table = jax.random.normal(ks[0], (V, D), jnp.float32)
    ids = jax.random.randint(ks[1], (B, F, NNZ), 0, V)
    mask = (jax.random.uniform(ks[2], (B, F, NNZ)) < 0.7).astype(jnp.float32)
    mask = mask.at[:, :, 0].set(1.0)
    out = embedding_bag_pallas(table, ids, mask, combiner=combiner,
                               interpret=True)
    want = ref.embedding_bag_reference(table, ids, mask, combiner=combiner)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# -- triple scan -------------------------------------------------------------------

def _cols(triples):
    """[T, 3] rows -> the kernels' (s, p, o) int32 column layout."""
    return tuple(jnp.asarray(np.asarray(triples)[:, c], jnp.int32)
                 for c in range(3))


SCAN_PATTERNS = [(-1, 3, -1), (7, -1, -1), (-1, -1, -1), (1, 2, 3),
                 (-1, 4, 9)]


@pytest.mark.parametrize("T", [100, 2048, 5000])
def test_triple_scan_vs_ref(T):
    rng = np.random.default_rng(0)
    cols = _cols(rng.integers(0, 50, (T, 3)))
    for (s, p, o) in SCAN_PATTERNS:
        out = triple_scan(cols, jnp.asarray([s, p, o]), bt=1024,
                          interpret=True)
        want = ref.triple_scan_reference(cols, s, p, o)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


@pytest.mark.parametrize("Q", [1, 5, 8, 13])
def test_triple_scan_many_vs_ref(Q):
    """Any pattern count is legal: Q <= 8 is one tile of Q rows, larger Q
    pads to whole groups of 8 whose extra rows are sliced away."""
    rng = np.random.default_rng(Q)
    cols = _cols(rng.integers(0, 20, (3000, 3)))
    pats = rng.integers(-1, 20, (Q, 3)).astype(np.int32)
    pats[0] = (-1, -1, -1)
    out = np.asarray(triple_scan_many(cols, jnp.asarray(pats), bt=1024,
                                      interpret=True))
    assert out.shape == (Q, 3000)
    for q in range(Q):
        np.testing.assert_array_equal(
            out[q], np.asarray(ref.triple_scan_reference(cols, *pats[q])))


def test_padding_rows_never_match():
    """Rows with a negative predicate are staging padding: no pattern,
    not even the all-wildcard one, matches them."""
    cols = _cols(np.asarray([[1, 0, 2], [3, 1, 4], [-1, -1, -1]]))
    for pat in [(-1, -1, -1), (-1, 0, -1), (1, -1, -1)]:
        mask = np.asarray(triple_scan(cols, jnp.asarray(pat),
                                      interpret=True))
        assert mask[2] == 0
        assert mask[:2].tolist() == np.asarray(
            ref.triple_scan_reference(cols, *pat))[:2].tolist()


def test_scan_blocks_must_be_whole_tiles():
    """A 1-D int32 block must be a whole number of 1024-element tiles —
    the TPU compiler refuses anything else, so the wrappers refuse it
    first, in every mode."""
    cols = _cols(np.zeros((8, 3), np.int64))
    with pytest.raises(ValueError):
        triple_scan(cols, jnp.asarray([-1, -1, -1]), bt=512, interpret=True)
    with pytest.raises(ValueError):
        probe_sorted(jnp.zeros(4, jnp.int32), jnp.zeros(4, jnp.int32),
                     bk=512, interpret=True)


def test_triple_scan_agrees_with_matcher_candidates():
    """The kernel implements the matcher's candidate scan semantics."""
    from repro.rdf.generator import generate_watdiv_like
    g = generate_watdiv_like(scale=0.3, seed=5)
    cols = _cols(g.store.triples())
    pid = 3
    mask = triple_scan(cols, jnp.asarray([-1, pid, -1]), interpret=True)
    got = np.flatnonzero(np.asarray(mask))
    want = np.sort(g.store.pred_tids(pid))
    np.testing.assert_array_equal(got, want)


# -- sorted-probe join ---------------------------------------------------------

# (K, P): empty keys, single element, chunk boundaries around bk/bp
# multiples, sizes forcing multi-block accumulation
PROBE_CASES = [(0, 7), (1, 1), (100, 33), (512, 512), (513, 511),
               (2048, 129), (5000, 1000)]


@pytest.mark.parametrize("K,P", PROBE_CASES)
def test_probe_sorted_vs_searchsorted(K, P):
    """Bit-identical to the matcher's np.searchsorted join probe — with
    duplicate keys and probe values outside the key range on both sides."""
    rng = np.random.default_rng(K * 1009 + P)
    keys = np.sort(rng.integers(0, 60, K)).astype(np.int32)
    probes = rng.integers(-10, 90, P).astype(np.int32)
    lo, hi = probe_sorted(jnp.asarray(keys), jnp.asarray(probes),
                          bk=1024, bp=128, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(lo), np.searchsorted(keys, probes, side="left"))
    np.testing.assert_array_equal(
        np.asarray(hi), np.searchsorted(keys, probes, side="right"))
    # jnp oracle agrees with the numpy ground truth above
    rlo, rhi = ref.probe_sorted_reference(jnp.asarray(keys),
                                          jnp.asarray(probes))
    np.testing.assert_array_equal(np.asarray(lo), np.asarray(rlo))
    np.testing.assert_array_equal(np.asarray(hi), np.asarray(rhi))


def test_probe_sorted_many_vs_searchsorted():
    rng = np.random.default_rng(7)
    keys = np.sort(rng.integers(0, 500, 2777)).astype(np.int32)
    probes = rng.integers(-5, 600, (5, 300)).astype(np.int32)
    lo, hi = probe_sorted_many(jnp.asarray(keys), jnp.asarray(probes),
                               bk=1024, bp=128, interpret=True)
    for q in range(probes.shape[0]):
        np.testing.assert_array_equal(
            np.asarray(lo[q]), np.searchsorted(keys, probes[q], side="left"))
        np.testing.assert_array_equal(
            np.asarray(hi[q]), np.searchsorted(keys, probes[q], side="right"))


@pytest.mark.parametrize("T,K,bt", [(100, 50, 1024), (2500, 0, 1024),
                                    (2048, 2048, 1024), (33, 5, 2048)])
def test_scan_probe_fused_vs_ref(T, K, bt):
    """Fused scan+first-join kernel vs the unfused oracle: empty key
    columns, chunk-boundary block sizes, all-wildcard patterns, both
    probe columns."""
    rng = np.random.default_rng(T + K)
    cols = _cols(rng.integers(0, 60, (T, 3)))
    keys = jnp.asarray(np.sort(rng.integers(0, 60, K)), jnp.int32)
    for pat in [(-1, 3, -1), (-1, -1, -1), (7, 2, -1), (1, 2, 3)]:
        for col in (0, 2):
            m, lo, hi = scan_probe(cols, jnp.asarray(pat, jnp.int32),
                                   keys, col, bt=bt, bk=bt, interpret=True)
            wm, wlo, whi = ref.scan_probe_reference(cols, *pat, keys, col)
            np.testing.assert_array_equal(np.asarray(m), np.asarray(wm))
            np.testing.assert_array_equal(np.asarray(lo), np.asarray(wlo))
            np.testing.assert_array_equal(np.asarray(hi), np.asarray(whi))


def test_scan_probe_rejects_predicate_column():
    with pytest.raises(ValueError):
        scan_probe(_cols(np.zeros((8, 3), np.int64)),
                   jnp.asarray([-1, -1, -1], jnp.int32),
                   jnp.zeros(4, jnp.int32), col=1, interpret=True)


@pytest.mark.requires_accelerator
def test_probe_sorted_compiled_matches_interpret():
    """Compiled (Mosaic) and interpret mode agree — runs on real hardware
    only; the CPU CI lane auto-skips via the marker."""
    rng = np.random.default_rng(0)
    keys = jnp.asarray(np.sort(rng.integers(0, 500, 4096)), jnp.int32)
    probes = jnp.asarray(rng.integers(-5, 600, 1024), jnp.int32)
    ci = probe_sorted(keys, probes, interpret=True)
    cc = probe_sorted(keys, probes, interpret=False)
    for a, b in zip(ci, cc):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.requires_accelerator
def test_triple_scan_compiled_matches_interpret():
    rng = np.random.default_rng(1)
    cols = _cols(rng.integers(0, 50, (4096, 3)))
    pat = jnp.asarray([-1, 3, -1])
    np.testing.assert_array_equal(
        np.asarray(triple_scan(cols, pat, interpret=True)),
        np.asarray(triple_scan(cols, pat, interpret=False)))


# -- persistent compilation cache -------------------------------------------------

@pytest.mark.parametrize("placed", [False, True])
def test_compile_cache_placement(monkeypatch, tmp_path, placed):
    """``JAX_COMPILATION_CACHE_DIR`` places the cache and nothing else is
    set; without it the cache goes to the checkout's fixed directory."""
    from repro.kernels import REPO_CACHE_DIR, use_compile_cache

    saved = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs")}
    if placed:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        got = use_compile_cache()
        now = jax.config.jax_compilation_cache_dir
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
    if placed:
        assert got == str(tmp_path)
        assert now == saved["jax_compilation_cache_dir"]
    else:
        assert got == now == str(REPO_CACHE_DIR)
        assert (REPO_CACHE_DIR.parent / "chip_smoke.py").is_file()
