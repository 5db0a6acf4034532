"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention_tpu
from repro.kernels.kv_copy import kv_copy_tpu
from repro.kernels.paged_attention import paged_attention_tpu

RNG = np.random.default_rng(0)


def _tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 \
        else dict(atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,Sq,Skv,H,D,causal,window", [
    (2, 64, 64, 2, 32, True, 0),
    (1, 40, 40, 3, 16, True, 0),          # non-multiple of block
    (2, 32, 96, 2, 32, True, 0),          # kv longer than q (chunked prefill)
    (1, 64, 64, 2, 64, True, 24),         # sliding window
    (2, 48, 48, 1, 16, False, 0),         # encoder (non-causal)
])
def test_flash_attention_sweep(B, Sq, Skv, H, D, causal, window, dtype):
    q = jnp.asarray(RNG.standard_normal((B, Sq, H, D)), dtype)
    k = jnp.asarray(RNG.standard_normal((B, Skv, H, D)), dtype)
    v = jnp.asarray(RNG.standard_normal((B, Skv, H, D)), dtype)
    out = flash_attention_tpu(q, k, v, causal=causal, window=window,
                              block_q=16, block_k=16)
    want = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,Hkv,D,P,NB,MB,cls", [
    (2, 8, 2, 32, 8, 16, 4, None),
    (3, 4, 4, 16, 16, 32, 3, None),     # MHA
    (1, 16, 2, 64, 8, 12, 6, None),
    (4, 8, 1, 32, 16, 24, 2, None),     # MQA
    # tiles of 4 blocks over a 6-wide table: contexts end mid-tile and
    # mid-block, one lane fills exactly one block
    (3, 8, 2, 32, 8, 40, 6, [45, 8, 17]),
    (4, 4, 2, 16, 8, 24, 4, [1, 30, 1, 9]),     # lanes with context 1
    (2, 4, 1, 16, 16, 8, 3, [48, 20]),   # MB < the byte budget's n, odd MB
    (2, 8, 4, 16, 8, 6, 1, [5, 8]),      # one-entry table
])
def test_paged_attention_sweep(B, H, Hkv, D, P, NB, MB, cls, dtype):
    q = jnp.asarray(RNG.standard_normal((B, H, D)), dtype)
    pool = jnp.asarray(RNG.standard_normal((NB, 2, P, Hkv, D)), dtype)
    bt = jnp.asarray(RNG.permutation(NB)[:B * MB].reshape(B, MB), jnp.int32)
    cl = jnp.asarray(RNG.integers(1, MB * P + 1, B) if cls is None else cls,
                     jnp.int32)
    out = paged_attention_tpu(q, pool, bt, cl)
    want = ref.paged_attention_ref(q, pool, bt, cl)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("MB,block_bytes,n", [
    (256, 65536, 16),        # qwen2.5-32b / yi-34b bf16 rows of one layer
    (512, 32768, 32),        # the same rows in int8
    (6, 64, 4),              # bounded by the table: the largest pow2 <= MB
    (3, 64, 2),
    (1, 64, 1),
    (8, 1 << 21, 1),         # one block past the budget still gets a tile
])
def test_blocks_per_tile(MB, block_bytes, n):
    from repro.kernels.paged_attention import blocks_per_tile
    assert blocks_per_tile(MB, block_bytes) == n


def test_paged_attention_matches_dense_flash():
    """Paged (block-first) result == dense attention over the same tokens."""
    B, H, Hkv, D, P, MB = 2, 4, 2, 16, 8, 4
    S = MB * P
    k = jnp.asarray(RNG.standard_normal((B, S, Hkv, D)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((B, S, Hkv, D)), jnp.float32)
    q = jnp.asarray(RNG.standard_normal((B, H, D)), jnp.float32)
    # build the block-first pool from dense k/v
    pool = np.zeros((B * MB, 2, P, Hkv, D), np.float32)
    bt = np.zeros((B, MB), np.int32)
    nb = 0
    for b in range(B):
        for j in range(MB):
            pool[nb, 0] = np.asarray(k[b, j * P:(j + 1) * P])
            pool[nb, 1] = np.asarray(v[b, j * P:(j + 1) * P])
            bt[b, j] = nb
            nb += 1
    cl = jnp.asarray([S, S - 5], jnp.int32)
    out = paged_attention_tpu(q, jnp.asarray(pool), jnp.asarray(bt), cl)
    grp = H // Hkv
    want = ref.flash_attention_ref(q[:, None], jnp.repeat(k, grp, 2),
                                   jnp.repeat(v, grp, 2), causal=False,
                                   kv_len=None)
    # manual mask for per-request lens via the paged ref instead:
    want2 = ref.paged_attention_ref(q, jnp.asarray(pool), jnp.asarray(bt), cl)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want2), atol=1e-5)


@pytest.mark.parametrize("cls", [None, [23, 1], [17, 24]],
                         ids=["random", "mid_tile_and_context_1", "mid_tile"])
def test_paged_attention_layered_pool(cls):
    """layer= addresses a (NB, L, 2, P, Hkv, D) multi-layer pool: each layer
    slice must match the flat-pool kernel on that slice."""
    B, H, Hkv, D, P, NB, MB, L = 2, 4, 2, 16, 8, 12, 3, 3
    q = jnp.asarray(RNG.standard_normal((B, H, D)), jnp.float32)
    pool = jnp.asarray(RNG.standard_normal((NB, L, 2, P, Hkv, D)),
                       jnp.float32)
    bt = jnp.asarray(RNG.permutation(NB)[:B * MB].reshape(B, MB), jnp.int32)
    cl = jnp.asarray(RNG.integers(1, MB * P + 1, B) if cls is None else cls,
                     jnp.int32)
    for l in range(L):
        out = paged_attention_tpu(q, pool, bt, cl, layer=l)
        want = ref.paged_attention_ref(q, pool[:, l], bt, cl)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)


def test_paged_attention_never_reads_past_the_context():
    """Poison: the trash row and every block-table slot past a lane's
    context hold NaN (as can a stale row), and a lane with context 0 reads
    nothing. Each real lane must still equal the reference over a clean
    pool, so no padded slot was read (0 * NaN in p @ V would show)."""
    B, H, Hkv, D, P, L, MB = 4, 8, 2, 16, 8, 2, 6
    lens = [45, 1, 9, 0]                 # mid-tile, one token, one block + 1
    NB = B * MB + 2
    trash = NB - 1
    q = jnp.asarray(RNG.standard_normal((B, H, D)), jnp.float32)
    clean = RNG.standard_normal((NB, L, 2, P, Hkv, D)).astype(np.float32)
    rows = RNG.permutation(NB - 1)[:B * MB].reshape(B, MB)
    bt = np.full((B, MB), trash, np.int32)
    poison = clean.copy()
    poison[trash] = np.nan
    for b, c in enumerate(lens):
        live = -(-c // P)
        bt[b, :live] = rows[b, :live]
        poison[rows[b, live:]] = np.nan      # rows only slots past c name
        bt[b, live:live + 2] = rows[b, live:live + 2]
    cl = jnp.asarray(lens, jnp.int32)
    ref_bt = np.where(np.isnan(poison[bt][..., 0, 0, 0, 0, 0]), 0, bt)
    for l in range(L):
        out = np.asarray(paged_attention_tpu(q, jnp.asarray(poison),
                                             jnp.asarray(bt), cl, layer=l))
        want = np.asarray(ref.paged_attention_ref(
            q, jnp.asarray(clean[:, l]), jnp.asarray(ref_bt), cl))
        np.testing.assert_allclose(out[:3], want[:3], atol=2e-5, rtol=2e-5)
        assert np.all(out[3] == 0.0)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int8])
@pytest.mark.parametrize("NB,F,N", [(10, 24, 4), (6, 128, 6), (32, 64, 1)])
def test_kv_copy_sweep(NB, F, N, dtype):
    if dtype == jnp.int8:
        pool = jnp.asarray(RNG.integers(-100, 100, (NB, F)), dtype)
    else:
        pool = jnp.asarray(RNG.standard_normal((NB, F)), dtype)
    src = jnp.asarray(RNG.choice(NB, N, replace=False), jnp.int32)
    dst = jnp.asarray(RNG.choice(NB, N, replace=False), jnp.int32)
    # mark one descriptor invalid
    if N > 1:
        src = src.at[0].set(-1)
    out = kv_copy_tpu(pool, src, dst)
    want = ref.kv_copy_ref(pool, src, dst)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


@pytest.mark.parametrize("row_shape,dtype", [
    ((3, 2, 4, 2, 8), jnp.bfloat16),   # (L, 2, P, Hkv, D) pool row
    ((3, 2, 4, 2, 8), jnp.int8),       # int8 pool row
    ((3, 2, 2), jnp.float32),          # (L, 2, Hkv) int8-tier scale row
])
def test_kv_copy_pool_rows_match_ref(row_shape, dtype):
    """Multi-dim rows (the pool's own layout, copied slab by slab along
    dim 1) agree with the oracle, padded lanes included."""
    NB = 12
    if dtype == jnp.int8:
        pool = jnp.asarray(RNG.integers(-100, 100, (NB,) + row_shape), dtype)
    else:
        pool = jnp.asarray(RNG.standard_normal((NB,) + row_shape), dtype)
    src = jnp.asarray([4, 7, 1, -1], jnp.int32)
    dst = jnp.asarray([9, 2, 5, 11], jnp.int32)
    out = kv_copy_tpu(pool, src, dst)
    want = ref.kv_copy_ref(pool, src, dst)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


def test_kv_copy_padded_batch_leaves_other_rows_bit_identical():
    """The store pads a batch to a power of two with (src=-1, dst=trash)
    lanes: only the real destinations change — row 0 and every other row,
    the trash row included, keep their exact bits."""
    from repro.configs import ServingConfig, get_config
    from repro.serving.paged_runner import PagedKVStore
    cfg = get_config("qwen2.5-32b").reduced()
    sv = ServingConfig(num_hbm_blocks=10, block_size=4)
    store = PagedKVStore(cfg, sv, jnp.bfloat16, staging=4)
    before = np.asarray(RNG.standard_normal(store.pool.shape), np.float32)
    store.pool = jnp.asarray(before, jnp.bfloat16)
    before = np.asarray(store.pool)
    src, dst = [3, 8, 5], [6, 1, 9]                  # 3 lanes -> padded to 4
    store._copy_rows(src, dst)
    after = np.asarray(store.pool)
    for s_, d_ in zip(src, dst):
        np.testing.assert_array_equal(after[d_], before[s_])
    untouched = [r for r in range(after.shape[0]) if r not in dst]
    assert 0 in untouched and store.trash_row in untouched
    np.testing.assert_array_equal(after[untouched], before[untouched])


def test_ops_dispatch_cpu_uses_ref():
    q = jnp.zeros((1, 8, 2, 16), jnp.float32)
    out = ops.flash_attention(q, q, q)           # auto => ref on CPU
    out2 = ops.flash_attention(q, q, q, force="pallas")
    np.testing.assert_allclose(np.asarray(out), np.asarray(out2), atol=1e-5)
