"""Pallas kernels vs pure-jnp oracles (interpret mode on CPU).

Each kernel is swept over shapes and dtypes and asserted allclose against
its ref.py oracle (deliverable c)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.kmeans_assign import (kmeans_assign_pallas,
                                         kmeans_assign_reduce_pallas)
from repro.kernels.router_utility import router_utility_pallas


@pytest.fixture(scope="module", autouse=True)
def _release_compiled_programs():
    # Interpret-mode pallas_call programs (and the token-parity decode
    # rollouts below) compile large XLA graphs; drop the executables when
    # the module finishes so the full-suite process doesn't carry them.
    yield
    jax.clear_caches()


@pytest.mark.parametrize("n,d,K", [(64, 8, 3), (513, 77, 13), (1000, 128, 20),
                                   (256, 768, 15), (37, 33, 40)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kmeans_assign(n, d, K, dtype):
    kx, kc = jax.random.split(jax.random.PRNGKey(n + d))
    x = jax.random.normal(kx, (n, d), dtype)
    c = jax.random.normal(kc, (K, d), dtype)
    got = kmeans_assign_pallas(x, c, interpret=True)
    want = ref.kmeans_assign_ref(x, c)
    # ties can differ between argmin orders at low precision — allow equal dist
    neq = np.asarray(got != want)
    if neq.any():
        xf, cf = np.asarray(x, np.float32), np.asarray(c, np.float32)
        d2 = ((xf[:, None] - cf[None]) ** 2).sum(-1)
        rows = np.where(neq)[0]
        assert np.allclose(d2[rows, np.asarray(got)[rows]],
                           d2[rows, np.asarray(want)[rows]], rtol=1e-3,
                           atol=1e-3)


@pytest.mark.parametrize("n,d,K", [(65, 1000, 7), (33, 1536, 5),
                                   (257, 999, 13)])
def test_kmeans_assign_wide_d_boundary(n, d, K):
    """Wide-d boundary: below ``block_d`` (default 2048) both kmeans
    kernels still run their original single-pass paths and must match the
    oracle exactly — pinned here so the d-tiling dispatch can never perturb
    the narrow/medium regime it leaves alone."""
    kx, kc, kw = jax.random.split(jax.random.PRNGKey(n), 3)
    x = jax.random.normal(kx, (n, d))
    c = jax.random.normal(kc, (K, d))
    w = jax.random.uniform(kw, (n,))
    np.testing.assert_array_equal(
        np.asarray(kmeans_assign_pallas(x, c, interpret=True)),
        np.asarray(ref.kmeans_assign_ref(x, c)))
    a_got, s_got, n_got = kmeans_assign_reduce_pallas(x, c, w,
                                                      interpret=True)
    a_ref, s_ref, n_ref = ref.kmeans_assign_reduce_ref(x, c, w)
    np.testing.assert_array_equal(np.asarray(a_got), np.asarray(a_ref))
    # wide-d sums accumulate n terms per coordinate — scale the tolerance
    np.testing.assert_allclose(np.asarray(s_got), np.asarray(s_ref),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(n_got), np.asarray(n_ref),
                               rtol=1e-5, atol=1e-5)


def _assert_assign_equiv(x, c, got, want):
    """Tiled accumulation reorders float sums, so an argmin may legally
    flip between equidistant (to rounding) centroids; anything else is a
    real mismatch."""
    got, want = np.asarray(got), np.asarray(want)
    neq = got != want
    if neq.any():
        xf, cf = np.asarray(x, np.float32), np.asarray(c, np.float32)
        d2 = ((xf[:, None] - cf[None]) ** 2).sum(-1)
        rows = np.where(neq)[0]
        assert np.allclose(d2[rows, got[rows]], d2[rows, want[rows]],
                           rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("n,d,K,bd", [(65, 300, 7, 128), (33, 1536, 5, 512),
                                      (257, 999, 13, 256),
                                      (100, 4096, 40, 2048)])
def test_kmeans_assign_block_d_tiled(n, d, K, bd):
    """d wider than ``block_d`` runs the d-tile accumulation loop (VMEM
    scratch holds the x·μᵀ and ‖μ‖² partials; the argmin merge waits for
    the last d tile) — same assignment as the oracle up to float ties,
    including non-pow2 d with padding and the block_k × block_d combined
    grid."""
    kx, kc, kw = jax.random.split(jax.random.PRNGKey(n), 3)
    x = jax.random.normal(kx, (n, d))
    c = jax.random.normal(kc, (K, d))
    w = jax.random.uniform(kw, (n,))
    got = kmeans_assign_pallas(x, c, block_n=64, block_d=bd, interpret=True)
    _assert_assign_equiv(x, c, got, ref.kmeans_assign_ref(x, c))

    a_got, s_got, n_got = kmeans_assign_reduce_pallas(
        x, c, w, block_n=64, block_d=bd, interpret=True)
    # reduce must be self-consistent with the kernel's own assignment
    # (ties may legally route a point to an equidistant cluster)
    np.testing.assert_array_equal(np.asarray(a_got), np.asarray(got))
    onehot = jax.nn.one_hot(a_got, K, dtype=jnp.float32) * w[:, None]
    np.testing.assert_allclose(np.asarray(s_got),
                               np.asarray(onehot.T @ x), rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(n_got),
                               np.asarray(onehot.sum(0)), rtol=1e-5,
                               atol=1e-5)


def test_kmeans_assign_block_d_shape_independence():
    """The assignment must not depend on the d tiling (up to exact-tie
    flips, checked by distance)."""
    kx, kc = jax.random.split(jax.random.PRNGKey(17))
    x = jax.random.normal(kx, (70, 900))
    c = jax.random.normal(kc, (9, 900))
    base = kmeans_assign_pallas(x, c, block_n=64, interpret=True)
    for bd in (128, 256, 512):
        got = kmeans_assign_pallas(x, c, block_n=64, block_d=bd,
                                   interpret=True)
        _assert_assign_equiv(x, c, got, base)


def test_attn_decode_step_kernel_dispatch(monkeypatch):
    """REPRO_KERNELS=pallas routes the uniform decode step (and therefore
    the engine's uniform decode scan) through the flash-decoding kernel —
    interpret mode on CPU — matching the jnp path for scalar and per-slot
    positions, with identical cache writes."""
    from repro.config import ModelConfig
    from repro.models import attention as A
    cfg = ModelConfig(name="dispatch-tiny", arch_type="dense", n_layers=1,
                      d_model=32, n_heads=2, n_kv_heads=1, d_ff=64,
                      vocab=97, head_dim=16, dtype="float32")
    p = A.init_attn(jax.random.PRNGKey(0), cfg)
    B, W = 3, 32
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    x = jax.random.normal(ks[0], (B, 1, cfg.d_model))
    cache = {"k": jax.random.normal(ks[1], (B, 1, W, 16)),
             "v": jax.random.normal(ks[2], (B, 1, W, 16))}
    for pos in (jnp.int32(5), jnp.array([3, 17, 31], jnp.int32)):
        monkeypatch.delenv("REPRO_KERNELS", raising=False)
        o_ref, c_ref_ = A.attn_decode_step(p, x, cache, pos, cfg,
                                           rolling=False)
        monkeypatch.setenv("REPRO_KERNELS", "pallas")
        o_k, c_k = A.attn_decode_step(p, x, cache, pos, cfg, rolling=False)
        np.testing.assert_allclose(np.asarray(o_ref), np.asarray(o_k),
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_array_equal(np.asarray(c_ref_["k"]),
                                      np.asarray(c_k["k"]))


def test_kmeans_assign_large_k_tiled():
    """Centroid tables bigger than one block run the block_k tile loop and
    still match the oracle exactly (strict-< merge keeps first-tie order)."""
    kx, kc = jax.random.split(jax.random.PRNGKey(3))
    x = jax.random.normal(kx, (200, 24))
    c = jax.random.normal(kc, (1000, 24))
    want = ref.kmeans_assign_ref(x, c)
    for bk in (128, 256, 512):
        got = kmeans_assign_pallas(x, c, block_k=bk, interpret=True)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("n,d,K", [(64, 8, 3), (513, 77, 13), (256, 128, 20),
                                   (100, 40, 130)])
def test_kmeans_assign_reduce(n, d, K):
    """Fused assign-reduce kernel == jnp oracle: same argmin, same
    weighted per-cluster coordinate sums and counts."""
    kx, kc, kw = jax.random.split(jax.random.PRNGKey(n + d), 3)
    x = jax.random.normal(kx, (n, d))
    c = jax.random.normal(kc, (K, d))
    w = jax.random.uniform(kw, (n,))
    a_ref, s_ref, n_ref = ref.kmeans_assign_reduce_ref(x, c, w)
    a_got, s_got, n_got = kmeans_assign_reduce_pallas(x, c, w,
                                                      interpret=True)
    np.testing.assert_array_equal(np.asarray(a_got), np.asarray(a_ref))
    np.testing.assert_allclose(np.asarray(s_got), np.asarray(s_ref),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(n_got), np.asarray(n_ref),
                               rtol=1e-5, atol=1e-5)


def test_kmeans_assign_reduce_large_k_tiled():
    """K in the thousands runs the two-phase block_k centroid-tile loop
    (tiled argmin merge, then tiled one-hot reduction) and still matches
    the whole-table oracle: exact argmin (strict-< keeps first-tie order)
    and allclose sums/counts."""
    kx, kc, kw = jax.random.split(jax.random.PRNGKey(11), 3)
    x = jax.random.normal(kx, (300, 24))
    c = jax.random.normal(kc, (2000, 24))
    w = jax.random.uniform(kw, (300,))
    a_ref, s_ref, n_ref = ref.kmeans_assign_reduce_ref(x, c, w)
    for bk in (128, 512, 1024):
        a_got, s_got, n_got = kmeans_assign_reduce_pallas(
            x, c, w, block_k=bk, interpret=True)
        np.testing.assert_array_equal(np.asarray(a_got), np.asarray(a_ref))
        np.testing.assert_allclose(np.asarray(s_got), np.asarray(s_ref),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(n_got), np.asarray(n_ref),
                                   rtol=1e-5, atol=1e-5)


def test_kmeans_assign_reduce_masks_padding():
    """Zero-weight (padded) rows must not leak into sums/counts, and the
    reduction must agree with a manual per-cluster sum."""
    x = jnp.asarray(np.random.default_rng(0).normal(size=(37, 9)),
                    jnp.float32)
    c = x[:5]
    w = jnp.where(jnp.arange(37) < 30, 1.0, 0.0)
    assign, sums, cnts = ref.kmeans_assign_reduce_ref(x, c, w)
    assert float(jnp.sum(cnts)) == pytest.approx(30.0)
    manual = np.zeros((5, 9), np.float32)
    for i in range(30):
        manual[int(assign[i])] += np.asarray(x[i])
    np.testing.assert_allclose(np.asarray(sums), manual, rtol=1e-5,
                               atol=1e-5)


@pytest.mark.parametrize("n,dh,M", [(17, 64, 3), (300, 512, 11), (256, 512, 14),
                                    (1024, 128, 40)])
@pytest.mark.parametrize("lam", [0.0, 0.5, 10.0])
def test_router_utility(n, dh, M, lam):
    keys = jax.random.split(jax.random.PRNGKey(n + M), 5)
    h = jax.random.normal(keys[0], (n, dh))
    aw = jax.random.normal(keys[1], (dh, M)) * 0.05
    ab = jax.random.normal(keys[2], (M,)) * 0.1
    cw = jax.random.normal(keys[3], (dh, M)) * 0.05
    cb = jax.random.normal(keys[4], (M,)) * 0.1
    c1, b1 = ref.router_utility_ref(h, aw, ab, cw, cb, lam)
    c2, b2 = router_utility_pallas(h, aw, ab, cw, cb, lam, interpret=True)
    np.testing.assert_allclose(np.asarray(b1), np.asarray(b2), rtol=2e-5,
                               atol=2e-5)
    # argmax may differ only on numerical ties
    neq = np.asarray(c1 != c2)
    assert neq.mean() < 0.01


@pytest.mark.parametrize("B,S,H,hd", [(1, 128, 2, 64), (2, 256, 4, 64),
                                      (2, 512, 2, 128)])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(B, S, H, hd, causal, dtype):
    ks = jax.random.split(jax.random.PRNGKey(B * S + H), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), dtype)
    k = jax.random.normal(ks[1], (B, S, H, hd), dtype)
    v = jax.random.normal(ks[2], (B, S, H, hd), dtype)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    got = flash_attention_pallas(q, k, v, causal=causal, block_q=64,
                                 block_k=128, interpret=True)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=tol,
                               atol=tol)


def test_flash_block_shape_independence():
    """Result must not depend on the BlockSpec tiling."""
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, (2, 256, 2, 64)) for kk in ks)
    outs = [flash_attention_pallas(q, k, v, causal=True, block_q=bq,
                                   block_k=bk, interpret=True)
            for bq, bk in [(32, 32), (64, 128), (256, 64), (128, 256)]]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(o),
                                   rtol=1e-5, atol=1e-5)


def test_ops_dispatch_ref_default_on_cpu():
    from repro.kernels import ops
    x = jnp.zeros((4, 8))
    c = jnp.zeros((2, 8))
    assert ops.kmeans_assign(x, c).shape == (4,)


@pytest.mark.parametrize("B,Hkv,g,S,hd", [(1, 2, 4, 256, 64), (2, 4, 1, 512, 128),
                                          (2, 1, 8, 1024, 64)])
@pytest.mark.parametrize("n_valid_frac", [0.3, 1.0])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention(B, Hkv, g, S, hd, n_valid_frac, dtype):
    from repro.kernels.decode_attention import decode_attention_pallas
    ks = jax.random.split(jax.random.PRNGKey(B * S), 3)
    q = jax.random.normal(ks[0], (B, Hkv, g, hd), dtype)
    kc = jax.random.normal(ks[1], (B, Hkv, S, hd), dtype)
    vc = jax.random.normal(ks[2], (B, Hkv, S, hd), dtype)
    nv = max(1, int(S * n_valid_frac))
    want = ref.decode_attention_ref(q, kc, vc, nv)
    got = decode_attention_pallas(q, kc, vc, nv, block_s=128, interpret=True)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=tol,
                               atol=tol)


def test_decode_attention_per_batch_n_valid():
    """A (B,) n_valid vector gives every batch row (continuous-batching
    pool slot) its own validity bound — equal to the scalar kernel run
    per-row."""
    from repro.kernels.decode_attention import decode_attention_pallas
    B, Hkv, g, S, hd = 3, 2, 2, 64, 32
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (B, Hkv, g, hd))
    kc = jax.random.normal(ks[1], (B, Hkv, S, hd))
    vc = jax.random.normal(ks[2], (B, Hkv, S, hd))
    nv = jnp.array([3, 40, 64], jnp.int32)
    got = decode_attention_pallas(q, kc, vc, nv, block_s=32, interpret=True)
    want = ref.decode_attention_ref(q, kc, vc, nv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5,
                               atol=2e-5)
    for b in range(B):
        row = decode_attention_pallas(q[b:b + 1], kc[b:b + 1], vc[b:b + 1],
                                      int(nv[b]), block_s=32, interpret=True)
        np.testing.assert_allclose(np.asarray(got[b]), np.asarray(row[0]),
                                   rtol=2e-5, atol=2e-5)


def test_decode_attention_ragged_validity_including_empty_rows():
    """Ragged per-slot validity with fully-invalid rows (n_valid = 0 — a
    drained pool slot): valid rows match the per-row scalar runs, empty
    rows emit exactly 0 in both kernel and oracle (no uniform-softmax
    garbage average)."""
    from repro.kernels.decode_attention import decode_attention_pallas
    B, Hkv, g, S, hd = 4, 2, 2, 64, 32
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(ks[0], (B, Hkv, g, hd))
    kc = jax.random.normal(ks[1], (B, Hkv, S, hd))
    vc = jax.random.normal(ks[2], (B, Hkv, S, hd))
    nv = jnp.array([0, 1, 37, 64], jnp.int32)
    got = decode_attention_pallas(q, kc, vc, nv, block_s=32, interpret=True)
    want = ref.decode_attention_ref(q, kc, vc, nv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5,
                               atol=2e-5)
    assert np.all(np.asarray(got)[0] == 0.0)
    assert np.all(np.asarray(want)[0] == 0.0)
    for b in range(1, B):
        row = decode_attention_pallas(q[b:b + 1], kc[b:b + 1], vc[b:b + 1],
                                      int(nv[b]), block_s=32, interpret=True)
        np.testing.assert_allclose(np.asarray(got[b]), np.asarray(row[0]),
                                   rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("B,Hkv,g,ps,npg,P", [(2, 2, 2, 8, 4, 12),
                                              (3, 1, 4, 16, 2, 5),
                                              (1, 2, 1, 32, 3, 4),
                                              (2, 8, 4, 16, None, 40),
                                              (2, 2, 6, 16, None, 120)])
def test_paged_decode_attention_matches_oracle(B, Hkv, g, ps, npg, P):
    """Paged kernel == gather oracle: B rows over random page tables and
    bounds, then five rows placed around the kernel's page block — bounds
    0, 1, exactly one block, one past a block, and past the table — whose
    entries past their valid pages name the trash page 0. ``npg=None``
    makes the table one and a half blocks wide, so its last block is cut
    short by the table's end."""
    from repro.kernels.decode_attention import (paged_block_pages,
                                                paged_decode_attention_pallas)
    hd = 32
    page_bytes = Hkv * ps * hd * 4
    if npg is None:
        npg = paged_block_pages(1 << 20, page_bytes) * 3 // 2
        assert npg % paged_block_pages(npg, page_bytes)
    bk = paged_block_pages(npg, page_bytes) * ps
    edge = np.array([0, 1, bk, bk + 1, npg * ps + ps])
    ks = jax.random.split(jax.random.PRNGKey(B * ps + npg), 3)
    q = jax.random.normal(ks[0], (B + len(edge), Hkv, g, hd))
    kp = jax.random.normal(ks[1], (P, Hkv, ps, hd))
    vp = jax.random.normal(ks[2], (P, Hkv, ps, hd))
    rng = np.random.default_rng(0)
    pt = rng.integers(0, P, size=(B + len(edge), npg))
    for r, n in enumerate(edge, B):
        pt[r, -(-n // ps):] = 0
    pt = jnp.asarray(pt, jnp.int32)
    nv = jnp.asarray(np.concatenate(
        [rng.integers(0, npg * ps + 1, size=(B,)), edge]), jnp.int32)
    got = paged_decode_attention_pallas(q, kp, vp, pt, nv, interpret=True)
    want = ref.paged_decode_attention_ref(q, kp, vp, pt, nv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5,
                               atol=2e-5)
    assert np.all(np.asarray(got)[B] == 0.0)


@pytest.mark.parametrize("B,Hkv,g,ps,npg,P", [(2, 2, 2, 8, 4, 12),
                                              (3, 1, 4, 16, 2, 5),
                                              (4, 2, 1, 8, 3, 6)])
def test_paged_seg_matches_gather_oracle(B, Hkv, g, ps, npg, P):
    """The copy-free segment-summed CPU formulation == the gather oracle
    over random tables — including duplicate page entries (counted with
    multiplicity on both sides) and fully-invalid rows (exact zeros)."""
    hd = 32
    ks = jax.random.split(jax.random.PRNGKey(B * ps + P), 3)
    q = jax.random.normal(ks[0], (B, Hkv, g, hd))
    kp = jax.random.normal(ks[1], (P, Hkv, ps, hd))
    vp = jax.random.normal(ks[2], (P, Hkv, ps, hd))
    rng = np.random.default_rng(1)
    pt = jnp.asarray(rng.integers(0, P, size=(B, npg)), jnp.int32)
    nv = jnp.asarray(rng.integers(0, npg * ps + 1, size=(B,)), jnp.int32)
    nv = nv.at[0].set(0)                        # pin one fully-invalid row
    got = ref.paged_decode_attention_seg_ref(q, kp, vp, pt, nv)
    want = ref.paged_decode_attention_ref(q, kp, vp, pt, nv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5,
                               atol=2e-6)
    assert np.all(np.asarray(got)[0] == 0.0)
    # a table with every row naming the SAME page twice still agrees
    pt_dup = jnp.tile(pt[:, :1], (1, npg))
    np.testing.assert_allclose(
        np.asarray(ref.paged_decode_attention_seg_ref(q, kp, vp, pt_dup, nv)),
        np.asarray(ref.paged_decode_attention_ref(q, kp, vp, pt_dup, nv)),
        rtol=2e-5, atol=2e-6)


def test_ops_paged_cpu_fallback_is_segment_summed(monkeypatch):
    """kops.paged_decode_attention's non-Pallas path dispatches to the
    seg formulation and stays within float noise of the gather oracle."""
    from repro.kernels import ops as kops
    monkeypatch.setenv("REPRO_KERNELS", "ref")
    B, Hkv, g, ps, npg, P, hd = 2, 2, 2, 8, 3, 5, 32
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(ks[0], (B, Hkv, g, hd))
    kp = jax.random.normal(ks[1], (P, Hkv, ps, hd))
    vp = jax.random.normal(ks[2], (P, Hkv, ps, hd))
    pt = jnp.asarray([[0, 1, 2], [3, 4, 0]], jnp.int32)
    nv = jnp.asarray([17, 24], jnp.int32)
    got = kops.paged_decode_attention(q, kp, vp, pt, nv)
    np.testing.assert_array_equal(
        np.asarray(got),
        np.asarray(ref.paged_decode_attention_seg_ref(q, kp, vp, pt, nv)))
    np.testing.assert_allclose(
        np.asarray(got),
        np.asarray(ref.paged_decode_attention_ref(q, kp, vp, pt, nv)),
        rtol=2e-5, atol=2e-6)


def test_paged_decode_attention_equals_contiguous():
    """A paged pool whose table lays pages out contiguously must equal the
    contiguous kernel on the equivalent (B, Hkv, S, hd) cache — paging is
    an addressing change, not a math change."""
    from repro.kernels.decode_attention import (decode_attention_pallas,
                                                paged_decode_attention_pallas)
    B, Hkv, g, ps, npg, hd = 2, 2, 2, 16, 4, 32
    S = ps * npg
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (B, Hkv, g, hd))
    kc = jax.random.normal(ks[1], (B, Hkv, S, hd))
    vc = jax.random.normal(ks[2], (B, Hkv, S, hd))
    # pool rows = each batch row's pages, in order
    kp = jnp.moveaxis(kc.reshape(B, Hkv, npg, ps, hd), 2, 1) \
            .reshape(B * npg, Hkv, ps, hd)
    vp = jnp.moveaxis(vc.reshape(B, Hkv, npg, ps, hd), 2, 1) \
            .reshape(B * npg, Hkv, ps, hd)
    pt = jnp.arange(B * npg, dtype=jnp.int32).reshape(B, npg)
    nv = jnp.array([23, 64], jnp.int32)
    got = paged_decode_attention_pallas(q, kp, vp, pt, nv, interpret=True)
    want = decode_attention_pallas(q, kc, vc, nv, block_s=ps, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5,
                               atol=2e-5)


def test_decode_attention_matches_model_decode():
    """Kernel semantics == attn_decode_step inner math (head-major cache)."""
    from repro.kernels.decode_attention import decode_attention_pallas
    B, Hkv, g, S, hd = 2, 2, 3, 64, 32
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (B, Hkv, g, hd))
    kc = jax.random.normal(ks[1], (B, Hkv, S, hd))
    vc = jax.random.normal(ks[2], (B, Hkv, S, hd))
    nv = 40
    got = decode_attention_pallas(q, kc, vc, nv, block_s=32, interpret=True)
    # manual grouped einsum (as in models/attention.attn_decode_step)
    s = jnp.einsum("bhgd,bhkd->bhgk", q, kc) * hd ** -0.5
    s = jnp.where(jnp.arange(S)[None, None, None, :] < nv, s, -1e30)
    want = jnp.einsum("bhgk,bhkd->bhgd", jax.nn.softmax(s, -1), vc)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5,
                               atol=2e-5)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_decode_kernel_token_parity_uniform(monkeypatch, dtype):
    """Greedy TOKENS through the uniform decode path must be identical
    whether attention runs the Pallas flash-decoding kernel (interpret
    mode here) or the jnp reference einsum — the kernels share the jnp
    path's dtype discipline (cache-dtype dots, f32 accumulation, probs
    downcast before the V dot), so score/weight quantization matches and
    bf16 near-ties cannot split the argmax across the dispatch boundary.
    Values still differ in the last ulps (online softmax normalizes once
    at the end); the serving contract is about tokens, so that is what
    this pins."""
    from repro.config import ModelConfig
    from repro.models import init_params, model as mdl
    cfg = ModelConfig(name=f"ktok-{dtype}", arch_type="dense", n_layers=2,
                      d_model=32, n_heads=2, n_kv_heads=1, d_ff=64,
                      vocab=97, head_dim=16, dtype=dtype)
    params = init_params(jax.random.PRNGKey(0), cfg)
    B, W, steps = 3, 32, 12
    pos0 = np.array([3, 9, 17], np.int32)
    tok0 = np.array([5, 41, 88], np.int32)

    def rollout(impl):
        monkeypatch.setenv("REPRO_KERNELS", impl)
        cache = mdl.init_decode_cache(cfg, B, W)
        # make prior positions attention-valid with deterministic junk
        cache = jax.tree.map(
            lambda a: jax.random.normal(jax.random.PRNGKey(1), a.shape,
                                        a.dtype) * 0.3, cache)
        tok, pos = jnp.asarray(tok0), jnp.asarray(pos0)
        seq = []
        for _ in range(steps):
            logits, cache = mdl.decode_step(params, cache, cfg,
                                            tokens=tok[:, None], pos=pos)
            tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            pos = pos + 1
            seq.append(np.asarray(tok))
        return np.stack(seq, 1)

    np.testing.assert_array_equal(rollout("ref"), rollout("pallas"))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_decode_kernel_token_parity_paged(monkeypatch, dtype):
    """Paged twin of test_decode_kernel_token_parity_uniform: the
    scalar-prefetch paged kernel and the jnp gather path must emit the
    same greedy tokens on f32 AND bf16 pools."""
    from repro.config import ModelConfig
    from repro.models import init_params, model as mdl
    cfg = ModelConfig(name=f"ktokp-{dtype}", arch_type="dense", n_layers=2,
                      d_model=32, n_heads=2, n_kv_heads=1, d_ff=64,
                      vocab=97, head_dim=16, dtype=dtype)
    params = init_params(jax.random.PRNGKey(0), cfg)
    B, ps, npg, P, steps = 2, 8, 4, 9, 10
    pt = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)
    pos0 = np.array([4, 11], np.int32)
    tok0 = np.array([7, 61], np.int32)

    def rollout(impl):
        monkeypatch.setenv("REPRO_KERNELS", impl)
        cache = mdl.init_paged_cache(cfg, P, ps)
        cache = jax.tree.map(
            lambda a: jax.random.normal(jax.random.PRNGKey(2), a.shape,
                                        a.dtype) * 0.3, cache)
        tok, pos = jnp.asarray(tok0), jnp.asarray(pos0)
        seq = []
        for _ in range(steps):
            logits, cache = mdl.decode_step_paged(
                params, cache, cfg, tokens=tok[:, None], page_table=pt,
                pos=pos)
            tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            pos = pos + 1
            seq.append(np.asarray(tok))
        return np.stack(seq, 1)

    np.testing.assert_array_equal(rollout("ref"), rollout("pallas"))
