"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import flash_attention_train
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.lora_matmul import lora_matmul, lora_matmul_ref
from repro.kernels.ssd_scan import ssd_scan, ssd_sequential_ref

TOLS = {jnp.float32: dict(atol=2e-5, rtol=2e-5),
        jnp.bfloat16: dict(atol=3e-2, rtol=3e-2)}


GRAD_TOLS = {jnp.float32: dict(atol=2e-4, rtol=2e-4),
             jnp.bfloat16: dict(atol=2e-1, rtol=5e-2)}


def _lora_inputs(M, K, N, r, dtype):
    x = jax.random.normal(jax.random.key(M + N), (M, K),
                          jnp.float32).astype(dtype)
    w = (jax.random.normal(jax.random.key(1), (K, N)) * K ** -0.5).astype(dtype)
    a = (jax.random.normal(jax.random.key(2), (r, K)) * K ** -0.5).astype(dtype)
    b = jax.random.normal(jax.random.key(3), (N, r)).astype(dtype)
    return x, w, a, b


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("M,K,N,r", [(64, 128, 96, 4), (128, 64, 128, 8),
                                     (33, 70, 45, 1), (256, 256, 256, 6)])
def test_lora_matmul_sweep(M, K, N, r, dtype):
    x, w, a, b = _lora_inputs(M, K, N, r, dtype)
    yk = lora_matmul(x, w, a, b, scale=1.5, bm=64, bn=64, bk=64,
                     interpret=True, use_kernel=True)
    yr = lora_matmul_ref(x, w, a, b, 1.5)
    np.testing.assert_allclose(np.asarray(yk, np.float32),
                               np.asarray(yr, np.float32), **TOLS[dtype])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("M,K,N,r", [(64, 128, 96, 4),   # block-aligned-ish
                                     (33, 70, 45, 2),    # ragged everywhere
                                     (48, 64, 40, 1),    # ragged N, rank 1
                                     (128, 96, 64, 8)])
def test_lora_matmul_vjp_parity(M, K, N, r, dtype):
    """The fused custom VJP (dX kernel + rank-reduction kernels, interpret
    mode) must match the jnp oracle's autodiff for all four cotangents —
    including ragged shapes that exercise the padding path."""
    x, w, a, b = _lora_inputs(M, K, N, r, dtype)
    cot = jax.random.normal(jax.random.key(9), (M, N),
                            jnp.float32).astype(dtype)

    def fk(x, w, a, b):
        return lora_matmul(x, w, a, b, scale=1.25, bm=32, bn=32, bk=32,
                           interpret=True, use_kernel=True)

    yk, vjp_k = jax.vjp(fk, x, w, a, b)
    yr, vjp_r = jax.vjp(lambda *z: lora_matmul_ref(*z, 1.25), x, w, a, b)
    np.testing.assert_allclose(np.asarray(yk, np.float32),
                               np.asarray(yr, np.float32), **TOLS[dtype])
    for name, gk, gr in zip(("dx", "dw", "da", "db"), vjp_k(cot), vjp_r(cot)):
        assert gk.dtype == gr.dtype and gk.shape == gr.shape
        np.testing.assert_allclose(np.asarray(gk, np.float32),
                                   np.asarray(gr, np.float32),
                                   err_msg=name, **GRAD_TOLS[dtype])


def test_lora_matmul_vjp_cpu_fallback_matches_oracle():
    """The auto-dispatch path (off-TPU -> jnp fallback inside the same
    custom VJP) is what the fused trainers run on this container: grads
    must match the oracle's autodiff to f32 precision."""
    x, w, a, b = _lora_inputs(40, 56, 24, 4, jnp.float32)
    cot = jax.random.normal(jax.random.key(9), (40, 24))
    yk, vjp_k = jax.vjp(lambda *z: lora_matmul(*z, scale=0.5), x, w, a, b)
    yr, vjp_r = jax.vjp(lambda *z: lora_matmul_ref(*z, 0.5), x, w, a, b)
    np.testing.assert_allclose(np.asarray(yk), np.asarray(yr), atol=2e-5)
    for gk, gr in zip(vjp_k(cot), vjp_r(cot)):
        np.testing.assert_allclose(np.asarray(gk), np.asarray(gr), atol=2e-5,
                                   rtol=2e-5)


def test_lora_matmul_batched_lead_dims():
    x = jax.random.normal(jax.random.key(0), (2, 3, 40))
    w = jax.random.normal(jax.random.key(1), (40, 24)) * 0.1
    a = jax.random.normal(jax.random.key(2), (4, 40)) * 0.1
    b = jax.random.normal(jax.random.key(3), (24, 4))
    yk = lora_matmul(x, w, a, b, scale=1.0, bm=32, bn=32, bk=32,
                     interpret=True, use_kernel=True)
    yr = lora_matmul_ref(x.reshape(-1, 40), w, a, b, 1.0).reshape(2, 3, 24)
    np.testing.assert_allclose(np.asarray(yk), np.asarray(yr), atol=2e-5)


def test_lora_block_autotuner_memoizes_and_clips():
    from repro.kernels.lora_matmul import best_blocks
    from repro.kernels.lora_matmul.tune import _CACHE, clear_cache

    clear_cache()
    got = best_blocks(512, 1024, 1024, 8)
    assert got == best_blocks(512, 1024, 1024, 8)    # memo hit
    assert len(_CACHE) == 1
    bm, bn, bk = best_blocks(33, 70, 45, 2)          # ragged: tiles clipped
    assert bm <= 33 and bn <= 45 and bk <= 70
    # never a pathological tile: padded waste stays bounded for tiny shapes
    assert bm * bn * bk <= 128 ** 3


def _tuner_calls():
    from repro.kernels.flash_attention import tune as ft
    from repro.kernels.lora_matmul import tune as lt
    return {
        "lora": (lt.clear_cache,
                 lambda: lt.best_blocks(2048, 768, 3072, 4)),
        "lora_gather": (lt.clear_cache,
                        lambda: lt.best_gather_blocks(4, 768, 3072, 4, 4)),
        "flash_decode": (ft.clear_cache,
                         lambda: ft.best_decode_block(4, 12, 1, 128, 64)),
        "paged_decode": (ft.clear_paged_cache,
                         lambda: ft.best_paged_block(4, 12, 1, 8, 16, 64)),
        "flash_train": (ft.clear_train_cache,
                        lambda: ft.best_train_blocks(15, 12, 12, 512, 512,
                                                     64)),
    }


@pytest.mark.parametrize("tuner", ["lora", "lora_gather", "flash_decode",
                                   "paged_decode", "flash_train"])
def test_tuners_pick_the_same_tiles_on_every_backend(monkeypatch, tuner):
    """One deterministic rule on every backend: a CPU compile rehearsal
    lowers exactly the tiles the chip runs (the tuners are called while
    the step is traced, where no candidate can be timed)."""
    clear, pick = _tuner_calls()[tuner]
    picks = {}
    for name in ("cpu", "tpu", "gpu"):
        monkeypatch.setattr(jax, "default_backend", lambda n=name: n)
        clear()
        picks[name] = pick()
    clear()
    assert picks["cpu"] == picks["tpu"] == picks["gpu"], picks


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,Sq,Sk,H,KH,D,win",
                         [(2, 64, 64, 4, 2, 32, 0),
                          (1, 64, 128, 4, 1, 64, 0),
                          (2, 64, 64, 8, 8, 32, 24),
                          (1, 40, 72, 2, 1, 16, 0),
                          (1, 128, 128, 4, 2, 128, 33)])
def test_flash_attention_sweep(B, Sq, Sk, H, KH, D, win, dtype):
    key = jax.random.key(Sq + Sk)
    q = jax.random.normal(key, (B, Sq, H, D), jnp.float32).astype(dtype)
    k = jax.random.normal(jax.random.key(1), (B, Sk, KH, D),
                          jnp.float32).astype(dtype)
    v = jax.random.normal(jax.random.key(2), (B, Sk, KH, D),
                          jnp.float32).astype(dtype)
    # f32 tile operands (highest precision) for the f32 tolerances
    with jax.default_matmul_precision("highest"):
        o = flash_attention_train(q, k, v, window=win, bq=32, bk=32,
                                  interpret=True)
    oref = flash_attention_ref(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                               v.transpose(0, 2, 1, 3),
                               window=win).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(oref, np.float32), **TOLS[dtype])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,nh,hd,N,Q", [(2, 64, 4, 32, 16, 16),
                                           (1, 100, 2, 16, 8, 32),
                                           (2, 31, 3, 8, 4, 16),
                                           (1, 256, 2, 64, 32, 64)])
def test_ssd_scan_sweep(B, S, nh, hd, N, Q, dtype):
    key = jax.random.key(S)
    xh = jax.random.normal(key, (B, S, nh, hd), jnp.float32).astype(dtype)
    Bm = (jax.random.normal(jax.random.key(1), (B, S, N)) * N ** -0.5).astype(dtype)
    Cm = (jax.random.normal(jax.random.key(2), (B, S, N)) * N ** -0.5).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(jax.random.key(3), (B, S, nh)))
    A = -jnp.exp(jnp.linspace(0.0, 1.5, nh))
    yk = ssd_scan(xh, Bm, Cm, dt, A, chunk=Q)
    yr, _ = ssd_sequential_ref(xh, Bm, Cm, dt, A)
    tol = dict(atol=1e-4, rtol=1e-3) if dtype == jnp.float32 else \
        dict(atol=8e-2, rtol=8e-2)
    np.testing.assert_allclose(np.asarray(yk, np.float32),
                               np.asarray(yr, np.float32), **tol)


def test_kernels_match_model_twins(key):
    """The jnp twins inside the model (chunked attention / ssd_chunked) and
    the kernels agree with each other through the shared oracles."""
    from repro.models.attention import online_attention
    from repro.models.ssm import ssd_chunked

    B, Sq, H, KH, D = 1, 64, 4, 2, 32
    q = jax.random.normal(key, (B, Sq, H, D))
    k = jax.random.normal(jax.random.key(1), (B, Sq, KH, D))
    v = jax.random.normal(jax.random.key(2), (B, Sq, KH, D))
    pos = jnp.arange(Sq)
    o_model = online_attention(q, k, v, pos, pos, kv_chunk=16)
    with jax.default_matmul_precision("highest"):
        o_kernel = flash_attention_train(q, k, v, bq=32, bk=32,
                                         interpret=True)
    np.testing.assert_allclose(np.asarray(o_model), np.asarray(o_kernel),
                               atol=2e-5, rtol=2e-5)

    S, nh, hd, N = 64, 2, 16, 8
    xh = jax.random.normal(key, (1, S, nh, hd))
    Bm = jax.random.normal(jax.random.key(1), (1, S, N)) * N ** -0.5
    Cm = jax.random.normal(jax.random.key(2), (1, S, N)) * N ** -0.5
    dt = jax.nn.softplus(jax.random.normal(jax.random.key(3), (1, S, nh)))
    A = -jnp.exp(jnp.linspace(0.0, 1.0, nh))
    y_model, _ = ssd_chunked(xh, Bm, Cm, dt, A, chunk=16)
    y_kernel = ssd_scan(xh, Bm, Cm, dt, A, chunk=16)
    np.testing.assert_allclose(np.asarray(y_model), np.asarray(y_kernel),
                               atol=1e-4, rtol=1e-3)
