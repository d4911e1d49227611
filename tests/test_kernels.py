"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import make_shell_scene
from repro.core import soar
from repro.core.hashgrid import build_neighbor_table, kernel_offsets
from repro import engine
from repro.core.sparse_conv import (
    init_sparse_conv,
    reference_conv_cirf,
    submanifold_coir,
)
from repro.kernels.flash.flash import flash_attention
from repro.kernels.flash.ops import flash_attention_bshd
from repro.kernels.flash.ref import attention_ref
from repro.kernels.moe_gemm.moe_gemm import grouped_gemm
from repro.kernels.moe_gemm.ref import grouped_gemm_ref
from repro.kernels.sspnna.ref import sspnna_tile_ref
from repro.kernels.sspnna.sspnna import sspnna_tiles
from repro.sparse.tensor import from_dense


def _tol(dt):
    return (2e-2, 2e-2) if dt == jnp.bfloat16 else (1e-5, 1e-5)


@pytest.mark.parametrize("t,di,do,k,c,n,dt", [
    (3, 64, 32, 27, 16, 16, jnp.float32),
    (2, 96, 48, 27, 8, 24, jnp.float32),
    (4, 32, 32, 8, 32, 16, jnp.float32),
    (2, 64, 32, 27, 16, 16, jnp.bfloat16),
    (1, 16, 8, 27, 64, 64, jnp.float32),
])
def test_sspnna_kernel_vs_ref_sweep(rng, t, di, do, k, c, n, dt):
    feats = jnp.asarray(rng.normal(size=(t, di, c)), dt)
    idx = rng.integers(-1, di, (t, do, k)).astype(np.int32)
    w = jnp.asarray(rng.normal(size=(k, c, n)) * 0.1, dt)
    got = sspnna_tiles(feats, jnp.asarray(idx), w)
    ref = sspnna_tile_ref(feats, jnp.asarray(idx), w)
    rtol, atol = _tol(dt)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32), rtol=rtol, atol=atol)


def test_sspnna_full_conv_path(rng):
    dense = make_shell_scene(rng, 18, 12)
    t = from_dense(dense)
    coir = submanifold_coir(t, 18, 3)
    params = init_sparse_conv(jax.random.PRNGKey(0), 27, 12, 16)
    nbr = np.asarray(build_neighbor_table(
        t.coords, t.mask, jnp.asarray(kernel_offsets(3)), 18))
    order = soar.soar_order(nbr, np.asarray(t.mask), 64).order
    cp = engine.conv_plan_for_layer(coir, order, 64, 192)
    out = engine.sparse_conv(t.feats, params, cp, backend="sspnna",
                             use_kernel=True)
    ref = reference_conv_cirf(t.feats, coir, params)
    mask = np.asarray(t.mask)
    np.testing.assert_allclose(np.asarray(out)[mask], np.asarray(ref)[mask],
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("bh,sq,skv,d,causal,window,cap,dt", [
    (4, 256, 256, 64, True, None, None, jnp.float32),
    (2, 128, 256, 64, True, None, None, jnp.float32),
    (2, 256, 256, 64, True, 64, None, jnp.float32),
    (2, 256, 256, 64, True, None, 50.0, jnp.float32),
    (2, 256, 256, 128, False, None, None, jnp.float32),
    (2, 256, 256, 64, True, None, None, jnp.bfloat16),
    (1, 64, 512, 32, True, 128, 30.0, jnp.float32),
])
def test_flash_kernel_sweep(rng, bh, sq, skv, d, causal, window, cap, dt):
    q = jnp.asarray(rng.normal(size=(bh, sq, d)), dt)
    k = jnp.asarray(rng.normal(size=(bh, skv, d)), dt)
    v = jnp.asarray(rng.normal(size=(bh, skv, d)), dt)
    got = flash_attention(q, k, v, causal=causal, window=window, softcap=cap,
                          block_q=64, block_kv=64)
    ref = attention_ref(q[:, None], k[:, None], v[:, None], causal=causal,
                        window=window, softcap=cap)[:, 0]
    rtol, atol = _tol(dt)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32), rtol=rtol, atol=atol)


def test_flash_gqa_wrapper_matches_model_attention(rng):
    from repro.models.attention import chunked_attention

    b, s, hq, hkv, d = 2, 256, 8, 2, 32
    q = jnp.asarray(rng.normal(size=(b, s, hq, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, s, hkv, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, hkv, d)), jnp.float32)
    got = flash_attention_bshd(q, k, v, causal=True, block_q=64, block_kv=64)
    ref = chunked_attention(q, k, v, causal=True, q_chunk=64, kv_chunk=64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("e,c,d,f,bf,dt", [
    (4, 16, 32, 64, None, jnp.float32),
    (8, 8, 64, 128, 32, jnp.float32),
    (2, 32, 16, 48, 16, jnp.bfloat16),
])
def test_moe_grouped_gemm_sweep(rng, e, c, d, f, bf, dt):
    xin = jnp.asarray(rng.normal(size=(e, c, d)), dt)
    w = jnp.asarray(rng.normal(size=(e, d, f)) * 0.1, dt)
    valid = jnp.asarray(rng.random((e, c)) > 0.3)
    got = grouped_gemm(xin, w, valid, block_f=bf)
    ref = grouped_gemm_ref(xin, w, valid)
    rtol, atol = _tol(dt)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32), rtol=rtol, atol=atol)


def test_resolve_interpret_gates_on_backend(monkeypatch):
    from repro.kernels import runtime

    # explicit override always wins
    assert runtime.resolve_interpret(True) is True
    assert runtime.resolve_interpret(False) is False
    # default: compiled on TPU, interpreted everywhere else
    expected = jax.default_backend() != "tpu"
    assert runtime.resolve_interpret(None) is expected
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert runtime.resolve_interpret(None) is False
    # no environment variable can put a TPU into interpret mode
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    assert runtime.resolve_interpret(None) is False
    assert runtime.resolve_interpret(True) is True  # only the caller can
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert runtime.resolve_interpret(None) is True


def test_interpret_resolves_per_call_not_at_first_trace(monkeypatch):
    """The backend gate applies to later calls too: resolution happens in
    the unjitted wrapper, keying the jit cache on the concrete mode."""
    from repro.kernels.sspnna import sspnna as mod

    seen = {}

    def fake(feats, idx, w, *, interpret, **kw):
        seen["interpret"] = interpret

    monkeypatch.setattr(mod, "_sspnna_tiles", fake)
    mod.sspnna_tiles(None, None, None)
    assert seen["interpret"] is (jax.default_backend() != "tpu")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mod.sspnna_tiles(None, None, None)
    assert seen["interpret"] is False
    mod.sspnna_tiles(None, None, None, interpret=True)  # explicit still wins
    assert seen["interpret"] is True
