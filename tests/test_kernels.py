"""Per-kernel shape/dtype sweeps + hypothesis property tests, all in
interpret mode against the pure-jnp ref.py oracles (assignment (c))."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st  # hypothesis or deterministic fallback

from repro.kernels.decode_attention import counters as da_counters
from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.rwkv6_scan import rwkv6_scan_pallas
from repro.kernels.subtb_loss import subtb_loss_pallas
from repro.kernels.ref import (ref_decode_attention, ref_flash_attention,
                               ref_rwkv6, ref_subtb)
from repro.models.layers import chunked_linear_attention, flash_attention

KEY = jax.random.PRNGKey(0)


def rand(key, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


# ---------------------------------------------------------------------------
# Flash attention
# ---------------------------------------------------------------------------

FLASH_CASES = [
    # (B, Sq, Skv, H, KVH, D, causal, window, dtype, tol)
    (2, 128, 128, 4, 2, 64, True, 0, jnp.float32, 2e-5),
    (1, 100, 100, 8, 8, 32, True, 0, jnp.float32, 2e-5),
    (2, 64, 256, 4, 1, 128, False, 0, jnp.float32, 2e-5),
    (1, 256, 256, 4, 2, 64, True, 64, jnp.float32, 2e-5),
    (1, 64, 64, 2, 2, 64, True, 0, jnp.bfloat16, 3e-2),
    (1, 17, 33, 2, 1, 16, True, 0, jnp.float32, 2e-5),   # ragged
]


@pytest.mark.parametrize("case", FLASH_CASES, ids=str)
def test_flash_attention_matches_ref(case):
    B, Sq, Skv, H, KVH, D, causal, window, dtype, tol = case
    q = rand(KEY, (B, Sq, H, D), dtype)
    k = rand(jax.random.PRNGKey(1), (B, Skv, KVH, D), dtype)
    v = rand(jax.random.PRNGKey(2), (B, Skv, KVH, D), dtype)
    out = flash_attention_pallas(q, k, v, causal=causal, window=window,
                                 block_q=64, block_k=64)
    ref = ref_flash_attention(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol)


@settings(max_examples=15, deadline=None)
@given(sq=st.integers(4, 80), skv=st.integers(4, 80),
       h=st.sampled_from([1, 2, 4]), g=st.sampled_from([1, 2]),
       d=st.sampled_from([16, 32]), causal=st.booleans(),
       bq=st.sampled_from([16, 32]))
def test_flash_attention_property(sq, skv, h, g, d, causal, bq):
    H = h * g
    q = rand(KEY, (1, sq, H, d), jnp.float32)
    k = rand(jax.random.PRNGKey(1), (1, skv, h, d), jnp.float32)
    v = rand(jax.random.PRNGKey(2), (1, skv, h, d), jnp.float32)
    if causal and sq > skv:
        sq = skv
        q = q[:, :sq]
    out = flash_attention_pallas(q, k, v, causal=causal, block_q=bq,
                                 block_k=bq)
    ref = ref_flash_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)


def test_flash_attention_jnp_oracle_agrees():
    """The model-side chunked-jnp flash and the Pallas kernel agree (they
    share semantics; the model uses the jnp path on CPU, the kernel on TPU).
    """
    q = rand(KEY, (2, 96, 4, 32), jnp.float32)
    k = rand(jax.random.PRNGKey(1), (2, 96, 2, 32), jnp.float32)
    v = rand(jax.random.PRNGKey(2), (2, 96, 2, 32), jnp.float32)
    a = flash_attention(q, k, v, causal=True, chunk=32)
    b = flash_attention_pallas(q, k, v, causal=True, block_q=32, block_k=32)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-5)


# ---------------------------------------------------------------------------
# RWKV6 scan
# ---------------------------------------------------------------------------

RWKV_CASES = [
    # (B, T, H, Dk, Dv, chunk, bonus, dtype, tol)
    (2, 64, 2, 16, 16, 16, True, jnp.float32, 5e-4),
    (1, 100, 3, 32, 32, 32, True, jnp.float32, 5e-4),
    (2, 128, 2, 16, 64, 64, False, jnp.float32, 5e-4),
    (1, 48, 2, 16, 16, 16, True, jnp.bfloat16, 5e-2),
]


@pytest.mark.parametrize("case", RWKV_CASES, ids=str)
def test_rwkv6_matches_ref(case):
    B, T, H, Dk, Dv, chunk, bonus, dtype, tol = case
    r = rand(KEY, (B, T, H, Dk), dtype)
    k = rand(jax.random.PRNGKey(1), (B, T, H, Dk), dtype)
    v = rand(jax.random.PRNGKey(2), (B, T, H, Dv), dtype)
    w = (jax.nn.sigmoid(rand(jax.random.PRNGKey(3), (B, T, H, Dk),
                             jnp.float32)) * 0.6 + 0.35).astype(dtype)
    u = (0.1 * rand(jax.random.PRNGKey(4), (H, Dk), jnp.float32)
         ).astype(dtype) if bonus else None
    o, S = rwkv6_scan_pallas(r, k, v, w, u, chunk=chunk)
    o_ref, S_ref = ref_rwkv6(r, k, v, w, u)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(o_ref, np.float32), atol=tol)
    np.testing.assert_allclose(np.asarray(S), np.asarray(S_ref), atol=tol)


@settings(max_examples=10, deadline=None)
@given(t=st.integers(3, 70), h=st.sampled_from([1, 2]),
       dk=st.sampled_from([8, 16]), dv=st.sampled_from([8, 32]),
       chunk=st.sampled_from([8, 16, 32]))
def test_rwkv6_property(t, h, dk, dv, chunk):
    r = rand(KEY, (1, t, h, dk), jnp.float32)
    k = rand(jax.random.PRNGKey(1), (1, t, h, dk), jnp.float32)
    v = rand(jax.random.PRNGKey(2), (1, t, h, dv), jnp.float32)
    w = jax.nn.sigmoid(rand(jax.random.PRNGKey(3), (1, t, h, dk),
                            jnp.float32)) * 0.5 + 0.45
    o, S = rwkv6_scan_pallas(r, k, v, w, None, chunk=chunk)
    o_ref, S_ref = ref_rwkv6(r, k, v, w, None)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), atol=1e-3)
    np.testing.assert_allclose(np.asarray(S), np.asarray(S_ref), atol=1e-3)


def test_rwkv6_kernel_agrees_with_model_path():
    """Pallas kernel == model-side chunked jnp implementation."""
    r = rand(KEY, (2, 40, 2, 16), jnp.float32)
    k = rand(jax.random.PRNGKey(1), (2, 40, 2, 16), jnp.float32)
    v = rand(jax.random.PRNGKey(2), (2, 40, 2, 16), jnp.float32)
    w = jax.nn.sigmoid(rand(jax.random.PRNGKey(3), (2, 40, 2, 16),
                            jnp.float32)) * 0.5 + 0.45
    u = 0.1 * rand(jax.random.PRNGKey(4), (2, 16), jnp.float32)
    o1, S1 = rwkv6_scan_pallas(r, k, v, w, u, chunk=16)
    o2, S2 = chunked_linear_attention(r, k, v, w, u, chunk=16)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-4)
    np.testing.assert_allclose(np.asarray(S1), np.asarray(S2), atol=1e-4)


def test_rwkv6_state_chaining():
    """Running two halves with carried state == running the whole sequence."""
    T = 32
    r = rand(KEY, (1, T, 2, 16), jnp.float32)
    k = rand(jax.random.PRNGKey(1), (1, T, 2, 16), jnp.float32)
    v = rand(jax.random.PRNGKey(2), (1, T, 2, 16), jnp.float32)
    w = jax.nn.sigmoid(rand(jax.random.PRNGKey(3), (1, T, 2, 16),
                            jnp.float32)) * 0.5 + 0.45
    o_full, S_full = ref_rwkv6(r, k, v, w, None)
    o1, S1 = chunked_linear_attention(r[:, :16], k[:, :16], v[:, :16],
                                      w[:, :16], None, chunk=8)
    o2, S2 = chunked_linear_attention(r[:, 16:], k[:, 16:], v[:, 16:],
                                      w[:, 16:], None, state=S1, chunk=8)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([o1, o2], 1)),
                               np.asarray(o_full), atol=1e-4)
    np.testing.assert_allclose(np.asarray(S2), np.asarray(S_full), atol=1e-4)


# ---------------------------------------------------------------------------
# SubTB loss
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,T1,lam,block", [
    (4, 16, 0.9, 8), (3, 100, 0.8, 32), (2, 64, 0.99, 64), (1, 7, 0.5, 8)])
def test_subtb_matches_ref(B, T1, lam, block):
    phi = jax.random.normal(KEY, (B, T1))
    length = jax.random.randint(jax.random.PRNGKey(1), (B,), 1, T1)
    out = subtb_loss_pallas(phi, length, lam=lam, block=block)
    ref = ref_subtb(phi, length, lam)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


@settings(max_examples=15, deadline=None)
@given(t1=st.integers(3, 60), lam=st.floats(0.3, 0.99),
       block=st.sampled_from([8, 16, 64]))
def test_subtb_property(t1, lam, block):
    phi = jax.random.normal(KEY, (2, t1))
    length = jax.random.randint(jax.random.PRNGKey(1), (2,), 1, t1)
    out = subtb_loss_pallas(phi, length, lam=lam, block=block)
    ref = ref_subtb(phi, length, lam)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


def test_subtb_constant_phi_is_zero():
    """phi constant => every residual zero => loss exactly 0."""
    phi = jnp.full((2, 20), 3.14)
    length = jnp.array([10, 19])
    out = subtb_loss_pallas(phi, length, lam=0.9, block=8)
    np.testing.assert_allclose(np.asarray(out), 0.0, atol=1e-6)


# ---------------------------------------------------------------------------
# Decode attention (single-query KV-cache lookup)
# ---------------------------------------------------------------------------

DECODE_CASES = [
    # (B, S, H, D, block_k)
    (4, 16, 8, 8, 128),     # bitseq cache shape (L=15 + BOS)
    (2, 61, 8, 8, 16),      # AMP max_len=60 + BOS, tiled kv axis
    (3, 9, 4, 16, 8),       # TFBind8 + BOS, ragged block
    (1, 130, 2, 64, 128),   # kv axis > one block
    (256, 16, 8, 8, 128),   # bitseq training batch: four 64-row blocks
    (300, 16, 8, 8, 128),   # five 64-row blocks, the last one ragged
    (64, 130, 2, 64, 128),  # long, wide cache: the VMEM cap gives 8 rows
]


@pytest.mark.parametrize("case", DECODE_CASES, ids=str)
def test_decode_attention_matches_ref(case):
    B, S, H, D, block_k = case
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (B, H, D))
    k = jax.random.normal(ks[1], (B, S, H, D))
    v = jax.random.normal(ks[2], (B, S, H, D))
    kv_valid = jax.random.randint(ks[3], (B,), 1, S + 1)
    out = decode_attention_pallas(q, k, v, kv_valid, block_k=block_k)
    ref = ref_decode_attention(q, k, v, kv_valid)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@settings(max_examples=10, deadline=None)
@given(s=st.integers(2, 70), h=st.sampled_from([1, 2, 8]),
       d=st.sampled_from([8, 16, 64]), block_k=st.sampled_from([8, 32, 128]))
def test_decode_attention_property(s, h, d, block_k):
    ks = jax.random.split(jax.random.PRNGKey(s * 131 + h), 4)
    q = jax.random.normal(ks[0], (2, h, d))
    k = jax.random.normal(ks[1], (2, s, h, d))
    v = jax.random.normal(ks[2], (2, s, h, d))
    kv_valid = jax.random.randint(ks[3], (2,), 1, s + 1)
    out = decode_attention_pallas(q, k, v, kv_valid, block_k=block_k)
    ref = ref_decode_attention(q, k, v, kv_valid)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_decode_attention_single_valid_slot_returns_that_value():
    """With one valid slot the softmax is a delta: output == v[:, 0]."""
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (2, 4, 8))
    k = jax.random.normal(ks[1], (2, 10, 4, 8))
    v = jax.random.normal(ks[2], (2, 10, 4, 8))
    out = decode_attention_pallas(q, k, v, jnp.array([1, 1]))
    np.testing.assert_allclose(np.asarray(out), np.asarray(v[:, 0]),
                               atol=1e-6)


def test_decode_attention_empty_rows_in_a_wide_block_return_zeros():
    """Rows with ``kv_valid == 0`` among live rows of one 64-row block."""
    B, S, H, D = 64, 16, 8, 8
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (B, H, D))
    k = jax.random.normal(ks[1], (B, S, H, D))
    v = jax.random.normal(ks[2], (B, S, H, D))
    kv_valid = jax.random.randint(ks[3], (B,), 1, S + 1)
    empty = jnp.array([0, 5, 31, 32, 63])
    kv_valid = kv_valid.at[empty].set(0)
    out = decode_attention_pallas(q, k, v, kv_valid)
    ref = ref_decode_attention(q, k, v, kv_valid)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    assert np.all(np.asarray(out)[np.asarray(empty)] == 0.0)


@pytest.mark.parametrize("shape,rows", [
    ((16, 16, 8, 8), 16),      # bitseq at the recipe's 16 envs: one block
    ((256, 16, 8, 8), 64),     # bitseq at 256 envs: capped by the VMEM
    ((256, 61, 8, 8), 16),     # AMP's 61-slot cache (block_k 64)
    ((64, 130, 2, 64), 8),     # long, wide cache
], ids=str)
def test_decode_attention_row_block_counter(shape, rows):
    """Each traced call counts the rows per program it chose."""
    B, S, H, D = shape
    before = da_counters[f"rows_{rows}"]
    jax.eval_shape(lambda q, k, v, n: decode_attention_pallas(q, k, v, n),
                   jax.ShapeDtypeStruct((B, H, D), jnp.float32),
                   jax.ShapeDtypeStruct((B, S, H, D), jnp.float32),
                   jax.ShapeDtypeStruct((B, S, H, D), jnp.float32),
                   jax.ShapeDtypeStruct((B,), jnp.int32))
    assert da_counters[f"rows_{rows}"] == before + 1


def test_decode_attention_matches_cached_encoder_path():
    """The kernel is a drop-in for the jnp masked-softmax attention used by
    ``nn.transformer.encoder_query_cached`` (attn_impl='jnp' vs 'kernel')."""
    from repro.nn.transformer import (cache_init, decode_encoder_init,
                                      encoder_query_cached)
    p = decode_encoder_init(KEY, num_layers=2, dim=32, num_heads=4)
    x0 = jax.random.normal(KEY, (3, 32))
    cache = cache_init(p, x0, 9, num_heads=4)
    lengths = jnp.array([0, 3, 8])
    y_jnp = encoder_query_cached(p, cache, lengths, num_heads=4,
                                 attn_impl="jnp")
    y_ker = encoder_query_cached(p, cache, lengths, num_heads=4,
                                 attn_impl="kernel")
    np.testing.assert_allclose(np.asarray(y_jnp), np.asarray(y_ker),
                               atol=2e-5, rtol=2e-5)
