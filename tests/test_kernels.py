"""Kernel allclose sweeps: every Pallas kernel vs. its pure-jnp oracle.

Kernels run in interpret mode off the TPU (CPU executes the kernel body;
``repro.kernels.resolve_interpret`` decides it from the backend), oracles are
``repro.kernels.ref``.  Sweeps cover shapes (aligned + ragged), dtypes, and
GQA group structure; hypothesis drives property tests on the invariants.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ref, resolve_interpret
from repro.kernels.flash_attention import flash_attention
from repro.kernels.moe_gemm import moe_gemm
from repro.kernels.paged_attention import (contiguous_decode_attention,
                                           paged_decode_attention,
                                           paged_mla_decode_attention)
from repro.kernels.ssd_chunked import ssd_scan_chunked
from repro.kernels.ssd_scan import ssd_scan


def _rand(key, shape, dtype=jnp.float32, scale=1.0):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,T,H,KV,D,bq,bk", [
    (1, 128, 128, 4, 4, 32, 64, 64),     # MHA, aligned
    (2, 64, 64, 8, 2, 16, 32, 32),       # GQA 4:1
    (1, 96, 96, 4, 1, 32, 64, 32),       # MQA, ragged q blocks
    (1, 32, 160, 4, 2, 16, 32, 64),      # prefix kv longer than q
    (2, 8, 8, 2, 2, 128, 8, 8),          # tiny blocks
])
def test_flash_attention_matches_ref(B, S, T, H, KV, D, bq, bk, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = _rand(ks[0], (B, S, H, D), dtype)
    k = _rand(ks[1], (B, T, KV, D), dtype)
    v = _rand(ks[2], (B, T, KV, D), dtype)
    scale = D ** -0.5
    out = flash_attention(q, k, v, scale=scale, block_q=bq, block_k=bk)
    want = ref.flash_attention(q, k, v, scale)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@settings(max_examples=20, deadline=None)
@given(s=st.sampled_from([16, 48, 64]),
       h=st.sampled_from([2, 4]),
       g=st.sampled_from([1, 2]),
       d=st.sampled_from([8, 32]))
def test_flash_attention_property(s, h, g, d):
    """Row-stochastic invariance: attention over constant v returns v."""
    kv = h // g if h % g == 0 else 1
    ks = jax.random.split(jax.random.PRNGKey(s * h + d), 2)
    q = _rand(ks[0], (1, s, h, d))
    k = _rand(ks[1], (1, s, kv, d))
    v = jnp.ones((1, s, kv, d), jnp.float32) * 3.5
    out = flash_attention(q, k, v, scale=d ** -0.5, block_q=16, block_k=16)
    np.testing.assert_allclose(np.asarray(out), 3.5, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# decode attention (contiguous + paged)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,T,H,KV,D,bt", [
    (2, 128, 4, 4, 32, 64),
    (3, 256, 8, 2, 16, 64),
    (1, 96, 4, 1, 32, 32),               # MQA, ragged
    (2, 64, 16, 2, 64, 64),
])
def test_contiguous_decode_matches_ref(B, T, H, KV, D, bt, dtype):
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    q = _rand(ks[0], (B, 1, H, D), dtype)
    ck = _rand(ks[1], (B, T, KV, D), dtype)
    cv = _rand(ks[2], (B, T, KV, D), dtype)
    lengths = jax.random.randint(ks[3], (B,), 1, T + 1)
    out = contiguous_decode_attention(q, ck, cv, lengths, scale=D ** -0.5,
                                      block_t=bt)
    want = ref.decode_attention(q, ck, cv, lengths, D ** -0.5)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("B,H,KV,D,ps,npages", [
    (2, 4, 2, 32, 16, 8),
    (1, 8, 1, 16, 8, 12),
    (3, 4, 4, 64, 32, 4),
])
def test_paged_decode_matches_ref(B, H, KV, D, ps, npages):
    """Paged kernel vs paged oracle, with a shuffled page table."""
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    n_phys = B * npages + 3
    q = _rand(ks[0], (B, 1, H, D))
    pages = _rand(ks[1], (n_phys, ps, 2, KV, D))
    # each request gets a random non-overlapping set of physical pages
    perm = jax.random.permutation(ks[2], n_phys)[: B * npages]
    table = perm.reshape(B, npages).astype(jnp.int32)
    lengths = jax.random.randint(ks[3], (B,), 1, npages * ps + 1)
    # unmap pages beyond length (virtualizer invariant)
    needed = (lengths[:, None] > jnp.arange(npages)[None, :] * ps)
    table = jnp.where(needed, table, -1)
    out = paged_decode_attention(q, pages, table, lengths, scale=D ** -0.5)
    want = ref.paged_decode_attention(q, pages, table, lengths, D ** -0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("B,H,r,rp,ps,npages", [
    (2, 4, 16, 8, 8, 4),
    (1, 8, 32, 16, 16, 6),
    (3, 2, 8, 8, 4, 5),
])
def test_paged_mla_decode_matches_ref(B, H, r, rp, ps, npages):
    """Absorbed-MLA paged kernel vs its oracle, shuffled page table."""
    e = r + rp
    ks = jax.random.split(jax.random.PRNGKey(B * H + r), 4)
    n_phys = B * npages + 3
    q = _rand(ks[0], (B, 1, H, e))
    pages = _rand(ks[1], (n_phys, ps, e))
    perm = jax.random.permutation(ks[2], n_phys)[: B * npages]
    table = perm.reshape(B, npages).astype(jnp.int32)
    lengths = jax.random.randint(ks[3], (B,), 1, npages * ps + 1)
    needed = (lengths[:, None] > jnp.arange(npages)[None, :] * ps)
    table = jnp.where(needed, table, -1)
    out = paged_mla_decode_attention(q, pages, table, lengths,
                                     latent_dim=r, scale=e ** -0.5)
    want = ref.paged_mla_decode_attention(q, pages, table, lengths,
                                          r, e ** -0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_paged_equals_contiguous():
    """Paged attention over an identity page table == contiguous attention."""
    B, T, H, KV, D, ps = 2, 64, 4, 2, 16, 16
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    q = _rand(ks[0], (B, 1, H, D))
    ck = _rand(ks[1], (B, T, KV, D))
    cv = _rand(ks[2], (B, T, KV, D))
    lengths = jnp.array([40, 64], jnp.int32)
    npages = T // ps
    pages = jnp.stack(
        [ck.reshape(B, npages, ps, KV, D), cv.reshape(B, npages, ps, KV, D)],
        axis=3).reshape(B * npages, ps, 2, KV, D)
    table = jnp.arange(B * npages, dtype=jnp.int32).reshape(B, npages)
    out_p = paged_decode_attention(q, pages, table, lengths, scale=D ** -0.5)
    out_c = contiguous_decode_attention(q, ck, cv, lengths, scale=D ** -0.5)
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_c),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# paged KV write and the gather-first paged decode (kernels/ops.py)
# ---------------------------------------------------------------------------

PAGE_ELEMS = 32 * 1024                   # a 64 KiB bf16 page


def _element_scatter(pool, kv, pages, slots):
    """The element scatter ``ops.paged_kv_write`` replaced: one index per
    element, unmapped rows sent out of range and dropped."""
    e = kv.shape[-1]
    rows = jnp.where(pages >= 0, pages, pool.shape[0])
    cols = (slots * e)[:, None] + jnp.arange(e)[None, :]
    return pool.at[rows[:, None], cols].set(kv.astype(pool.dtype),
                                             mode="drop")


def _prompt_rows(e, n, table):
    """Pages and slots of a prompt's first ``n`` tokens: rows share pages."""
    tpp = PAGE_ELEMS // e
    toks = np.arange(n)
    return (np.asarray(table, np.int32)[toks // tpp],
            (toks % tpp).astype(np.int32))


@pytest.mark.parametrize("e,pages,slots", [
    (4096, [3, 0, 5, 2], [1, 7, 0, 4]),              # decode, distinct pages
    (288, *_prompt_rows(288, 230, [4, 1, 6])),       # a prompt sharing pages
    (4096, [2, -1, 4, -1], [3, 3, 0, 6]),            # unmapped rows dropped
    (288, [1, 3, -1], [112, 112, 112]),              # MLA slot 112: to 32544
    (4096, [5, 0], [7, 7]),                          # a GQA page's last slot
], ids=["decode", "prompt", "unmapped", "mla-last-slot", "gqa-last-slot"])
def test_paged_kv_write_matches_element_scatter(e, pages, slots):
    """The window scatter writes the same bf16 bits to the same slots as
    the element scatter, and leaves every other element as it was."""
    from repro.kernels import ops as kops
    ks = jax.random.split(jax.random.PRNGKey(e + len(pages)), 2)
    pool = _rand(ks[0], (7, PAGE_ELEMS), jnp.bfloat16)
    kv = _rand(ks[1], (len(pages), e), jnp.float32)
    pages = jnp.asarray(pages, jnp.int32)
    slots = jnp.asarray(slots, jnp.int32)
    want = _element_scatter(pool, kv, pages, slots)
    got = jax.jit(kops.paged_kv_write)(pool, kv, pages, slots)
    assert got.dtype == pool.dtype
    np.testing.assert_array_equal(np.asarray(got).view(np.uint16),
                                  np.asarray(want).view(np.uint16))
    assert not np.array_equal(np.asarray(got), np.asarray(pool))


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("kind", ["gqa", "mla"])
def test_gather_first_paged_decode_matches_whole_pool_view(kind, impl):
    """Gathering the table's pages from the untyped pool and typing only
    that block gives the same attention as typing the whole pool and
    gathering through the table, with unmapped entries and mixed lengths
    (bit for bit); the Pallas kernels, which take the whole pool typed,
    agree to their tolerance."""
    from repro.kernels import ops as kops
    B, H, page_elems, n_pages, max_pages = 3, 4, 1024, 11, 4
    ks = jax.random.split(jax.random.PRNGKey(7), 2)
    pool = _rand(ks[0], (n_pages, page_elems))
    if kind == "gqa":
        KV, D = 2, 16
        page_shape = (page_elems // (2 * KV * D), 2, KV, D)
        q = _rand(ks[1], (B, 1, H, D))
    else:
        r, rp = 16, 8                    # 42 tokens a page, 16 elems slack
        page_shape = (page_elems // (r + rp), r + rp)
        q = _rand(ks[1], (B, 1, H, r + rp))
    ps = page_shape[0]
    table = jnp.asarray([[7, 2, -1, -1], [0, 9, 4, 10], [5, -1, -1, -1]],
                        jnp.int32)
    lengths = jnp.asarray([ps + 3, 4 * ps, 1], jnp.int32)
    n = int(np.prod(page_shape))
    typed = pool[:, :n].reshape(n_pages, *page_shape)
    if kind == "gqa":
        want = ref.paged_decode_attention(q, typed, table, lengths, D ** -0.5)
        got = kops.paged_decode_attention(q, pool, table, lengths,
                                          page_shape=page_shape,
                                          scale=D ** -0.5, impl=impl)
    else:
        want = ref.paged_mla_decode_attention(q, typed, table, lengths, r,
                                              (r + rp) ** -0.5)
        got = kops.paged_mla_decode_attention(q, pool, table, lengths,
                                              page_shape=page_shape,
                                              latent_dim=r,
                                              scale=(r + rp) ** -0.5,
                                              impl=impl)
    if impl == "xla":
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    else:
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# grouped expert GEMM
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("N,K,M,E,bn,bm", [
    (256, 64, 128, 4, 64, 64),
    (128, 32, 64, 8, 32, 32),
    (96, 16, 48, 3, 32, 16),             # ragged everything
    (64, 128, 256, 2, 64, 128),
])
def test_moe_gemm_matches_ref(N, K, M, E, bn, bm, dtype):
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    x = _rand(ks[0], (N, K), dtype)
    w = _rand(ks[1], (E, K, M), dtype)
    # random ragged group sizes summing to N (some may be zero)
    cuts = np.sort(np.random.default_rng(N + E).integers(0, N + 1, E - 1))
    sizes = np.diff(np.concatenate([[0], cuts, [N]])).astype(np.int32)
    group_sizes = jnp.asarray(sizes)
    out = moe_gemm(x, w, group_sizes, block_n=bn, block_m=bm)
    want = ref.moe_gemm(x, w, group_sizes)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               **(_tol(dtype) if dtype == jnp.bfloat16
                                  else dict(rtol=1e-4, atol=1e-4)))


@settings(max_examples=15, deadline=None)
@given(e=st.integers(1, 6), n=st.sampled_from([32, 64]),
       seed=st.integers(0, 100))
def test_moe_gemm_property_block_identity(e, n, seed):
    """With w[e] = I for all e, grouped GEMM is the identity regardless of
    the grouping."""
    K = 16
    x = _rand(jax.random.PRNGKey(seed), (n, K))
    w = jnp.broadcast_to(jnp.eye(K)[None], (e, K, K))
    rng = np.random.default_rng(seed)
    cuts = np.sort(rng.integers(0, n + 1, e - 1))
    sizes = np.diff(np.concatenate([[0], cuts, [n]])).astype(np.int32)
    out = moe_gemm(x, w, jnp.asarray(sizes), block_n=16, block_m=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# Mamba2 SSD scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,H,P,G,N,chunk", [
    (1, 64, 4, 16, 1, 16, 16),
    (2, 128, 8, 8, 2, 32, 32),
    (1, 32, 2, 64, 1, 8, 8),
    (2, 96, 6, 16, 3, 16, 32),           # H % block_h clamps
])
def test_ssd_scan_kernel_matches_sequential_ref(B, S, H, P, G, N, chunk):
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    x = _rand(ks[0], (B, S, H, P), scale=0.5)
    dt = jax.nn.softplus(_rand(ks[1], (B, S, H), scale=0.5))
    A = -jnp.exp(_rand(ks[2], (H,), scale=0.3))
    B_ = _rand(ks[3], (B, S, G, N), scale=0.5)
    C_ = _rand(ks[4], (B, S, G, N), scale=0.5)
    y, h = ssd_scan(x, dt, A, B_, C_, chunk=chunk)
    y_ref, h_ref = ref.ssd_scan(x, dt, A, B_, C_)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref),
                               rtol=1e-3, atol=1e-3)


def test_ssd_chunked_jnp_matches_sequential_ref():
    """The scalable chunked formulation (used by models) vs the recurrence."""
    B, S, H, P, G, N = 2, 128, 4, 16, 1, 16
    ks = jax.random.split(jax.random.PRNGKey(6), 5)
    x = _rand(ks[0], (B, S, H, P), scale=0.5)
    dt = jax.nn.softplus(_rand(ks[1], (B, S, H), scale=0.5))
    A = -jnp.exp(_rand(ks[2], (H,), scale=0.3))
    B_ = _rand(ks[3], (B, S, G, N), scale=0.5)
    C_ = _rand(ks[4], (B, S, G, N), scale=0.5)
    y1, h1 = ssd_scan_chunked(x, dt, A, B_, C_, chunk=32)
    y2, h2 = ref.ssd_scan(x, dt, A, B_, C_)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2),
                               rtol=1e-4, atol=1e-4)


def test_ssd_scan_with_initial_state():
    """Chaining two half-sequences through h0 == one full scan (prefill
    semantics for the SSM-state 'cache')."""
    B, S, H, P, G, N = 1, 64, 2, 8, 1, 8
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    x = _rand(ks[0], (B, S, H, P), scale=0.5)
    dt = jax.nn.softplus(_rand(ks[1], (B, S, H), scale=0.5))
    A = -jnp.exp(_rand(ks[2], (H,), scale=0.3))
    B_ = _rand(ks[3], (B, S, G, N), scale=0.5)
    C_ = _rand(ks[4], (B, S, G, N), scale=0.5)
    y_full, h_full = ssd_scan(x, dt, A, B_, C_, chunk=16)
    half = S // 2
    y1, h1 = ssd_scan(x[:, :half], dt[:, :half], A, B_[:, :half], C_[:, :half],
                      chunk=16)
    y2, h2 = ssd_scan(x[:, half:], dt[:, half:], A, B_[:, half:], C_[:, half:],
                      chunk=16, h0=h1)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], axis=1)),
                               np.asarray(y_full), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(h2), np.asarray(h_full),
                               rtol=1e-4, atol=1e-4)


def test_interpret_mode_follows_the_backend():
    """Off the TPU a kernel interprets unless told not to (a compile for a
    described TPU passes ``interpret=False``); the choice is the backend's,
    made in one place."""
    assert jax.default_backend() != "tpu"
    assert resolve_interpret(None) is True
    assert resolve_interpret(False) is False
    assert resolve_interpret(True) is True
