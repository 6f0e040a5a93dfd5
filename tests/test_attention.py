"""Core attention: chunked-vs-direct equivalence, masks, GQA, KV cache."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from repro.testing.hypo import given, settings, strategies as st

from repro.models.attention import attention, init_kv_cache, update_kv_cache

RNG = np.random.default_rng(1)


def _qkv(B, Sq, Skv, nh, nkv, hd):
    return (jnp.asarray(RNG.normal(size=(B, Sq, nh, hd)), jnp.float32),
            jnp.asarray(RNG.normal(size=(B, Skv, nkv, hd)), jnp.float32),
            jnp.asarray(RNG.normal(size=(B, Skv, nkv, hd)), jnp.float32))


def test_chunked_equals_direct():
    q, k, v = _qkv(2, 512, 512, 4, 2, 32)
    direct = attention(q, k, v, chunk=4096)
    chunked = attention(q, k, v, chunk=128)
    np.testing.assert_allclose(direct, chunked, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("kind, window, softcap, hv, bwd_chunk", [
    ("causal", 0, 0.0, 16, 512),
    ("causal", 0, 0.0, 24, 32),
    ("local", 40, 0.0, 16, 32),
    ("causal", 0, 30.0, 16, 512),
    ("bidir", 0, 0.0, 24, 32),
])
def test_chunked_gradients_equal_direct(monkeypatch, kind, window, softcap,
                                        hv, bwd_chunk):
    """The chunked path's own backward (recomputed chunk by chunk from
    each row's log-sum-exp, in KV steps of ``BWD_CHUNK``) gives autodiff's
    gradients of the direct path: GQA, a value width of its own (as in
    MLA) and a given scale."""
    from repro.models import attention as attention_mod
    monkeypatch.setattr(attention_mod, "BWD_CHUNK", bwd_chunk)
    B, S, nh, nkv, hd = 2, 256, 4, 2, 16
    q, k, _ = _qkv(B, S, S, nh, nkv, hd)
    v = jnp.asarray(RNG.normal(size=(B, S, nkv, hv)), jnp.float32)
    w = jnp.asarray(RNG.normal(size=(B, S, nh, hv)), jnp.float32)

    def loss(chunk):
        return lambda q, k, v: jnp.sum(w * attention(
            q, k, v, kind=kind, window=window, softcap=softcap, chunk=chunk,
            scale=0.3))

    direct = jax.grad(loss(4096), argnums=(0, 1, 2))(q, k, v)
    chunked = jax.grad(loss(64), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(direct, chunked):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


def test_causal_mask_blocks_future():
    """Changing future tokens must not change past outputs."""
    q, k, v = _qkv(1, 64, 64, 2, 2, 16)
    out1 = attention(q, k, v)
    k2 = k.at[:, 32:].set(RNG.normal(size=(1, 32, 2, 16)))
    v2 = v.at[:, 32:].set(RNG.normal(size=(1, 32, 2, 16)))
    out2 = attention(q, k2, v2)
    np.testing.assert_allclose(out1[:, :32], out2[:, :32], atol=1e-6)
    assert not np.allclose(out1[:, 33:], out2[:, 33:])


def test_local_window_blocks_distant_past():
    q, k, v = _qkv(1, 128, 128, 2, 2, 16)
    out1 = attention(q, k, v, kind="local", window=16)
    # perturb tokens far outside the window of the last query
    k2 = k.at[:, :64].set(0.0)
    v2 = v.at[:, :64].set(0.0)
    out2 = attention(q, k2, v2, kind="local", window=16)
    np.testing.assert_allclose(out1[:, -1], out2[:, -1], atol=1e-6)


def test_gqa_equals_repeated_kv():
    """GQA must equal full MHA with kv heads explicitly repeated."""
    q, k, v = _qkv(2, 64, 64, 8, 2, 16)
    out_gqa = attention(q, k, v)
    k_rep = jnp.repeat(k, 4, axis=2)
    v_rep = jnp.repeat(v, 4, axis=2)
    out_full = attention(q, k_rep, v_rep)
    np.testing.assert_allclose(out_gqa, out_full, atol=1e-5, rtol=1e-5)


def test_kv_cache_decode_equals_full():
    """Prefill + single-token decode == full forward at that position."""
    B, S, nh, nkv, hd = 1, 33, 4, 2, 16
    q, k, v = _qkv(B, S, S, nh, nkv, hd)
    full = attention(q, k, v)

    cache = init_kv_cache(B, S, nkv, hd, jnp.float32)
    cache = update_kv_cache(cache, k[:, :S - 1], v[:, :S - 1], 0)
    cache = update_kv_cache(cache, k[:, S - 1:], v[:, S - 1:], S - 1)
    out = attention(q[:, S - 1:], cache["k"], cache["v"],
                    q_offset=S - 1, kv_len=S)
    np.testing.assert_allclose(out[:, 0], full[:, -1], atol=1e-5, rtol=1e-5)


def test_kv_len_masks_stale_cache():
    """Entries beyond kv_len (stale cache slots) must not contribute."""
    B, S = 1, 16
    q, k, v = _qkv(B, 1, S, 2, 2, 16)
    k_garbage = k.at[:, 8:].set(1e4)
    v_garbage = v.at[:, 8:].set(1e4)
    out1 = attention(q, k, v, q_offset=7, kv_len=8)
    out2 = attention(q, k_garbage, v_garbage, q_offset=7, kv_len=8)
    np.testing.assert_allclose(out1, out2, atol=1e-6)


@given(st.integers(1, 4), st.integers(1, 8), st.integers(8, 64))
@settings(max_examples=10)
def test_softmax_rows_bounded(B, nh, S):
    """Output is a convex combination of values: max |out| <= max |v|."""
    q = jnp.asarray(RNG.normal(size=(B, S, nh, 8)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(B, S, nh, 8)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(B, S, nh, 8)), jnp.float32)
    out = attention(q, k, v)
    assert float(jnp.max(jnp.abs(out))) <= float(jnp.max(jnp.abs(v))) + 1e-4
