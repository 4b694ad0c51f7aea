"""Training flash attention (kernels/flash_attention: forward with the f32
log-sum-exp, and the backward as one kernel or as dK/dV and dQ kernels,
behind one custom VJP) in Pallas interpret mode, against jnp references under the same operand contract,
and the model's dispatch to it (models/attention.py::run_attention)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import backend
from repro.kernels.flash_attention import (best_train_blocks,
                                           flash_attention_train)
from repro.kernels.flash_attention.ops import mxu_dtype_for
from repro.kernels.flash_attention.ref import (flash_attention_train_bwd_ref,
                                               flash_attention_train_ref)
from repro.models.attention import naive_attention, run_attention

# (B, S, H, KH, D, window, bq, bk, kv_heads): MHA and GQA, S on and off
# the blocks (padding), several tiles per row (skipped causal tiles and
# clamped index maps), windows that skip whole tiles on the left
CASES = {
    "mha": (2, 64, 4, 4, 32, 0, 32, 32, 2),
    "gqa": (1, 64, 4, 2, 32, 0, 32, 16, 1),
    "gqa_padded": (1, 72, 4, 2, 32, 0, 32, 32, 2),
    "mha_padded_uneven_blocks": (2, 40, 2, 2, 16, 0, 16, 32, 1),
    "window": (1, 96, 2, 1, 16, 33, 32, 16, 1),
    "window_padded": (1, 72, 4, 2, 32, 20, 16, 16, 1),
    # 256-row blocks: the causal fast path's 128 x 128 sub-tiles, below
    # and on the diagonal, with padding keys in the last diagonal block
    "gqa_subtiles_padded": (1, 300, 2, 1, 32, 0, 256, 256, 1),
    # one block each way: the single backward kernel, on sub-tiles and
    # on a whole masked tile
    "gqa_one_block_subtiles": (1, 200, 4, 2, 32, 0, 256, 256, 1),
    "window_one_block": (2, 48, 2, 2, 16, 12, 48, 48, 2),
}
# bf16 operands: the kernel rounds p per tile against the running max,
# the reference against the row max, so single elements differ by a
# bf16 step; f32 operands agree to accumulation order
TOL = {jnp.float32: dict(atol=2e-2, rtol=2e-2),
       jnp.bfloat16: dict(atol=6e-2, rtol=6e-2)}


def _inputs(case, dtype, seed=0):
    B, S, H, KH, D = CASES[case][:5]
    ks = jax.random.split(jax.random.key(seed), 4)
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (B, S, KH, D), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (B, S, KH, D), jnp.float32).astype(dtype)
    do = jax.random.normal(ks[3], (B, S, H, D), jnp.float32).astype(dtype)
    return q, k, v, do


def _kernel(case):
    window, bq, bk, kvh = CASES[case][5:]
    return lambda q, k, v: flash_attention_train(
        q, k, v, window=window, bq=bq, bk=bk, kv_heads=kvh, interpret=True)


def _t(x):
    return x.transpose(0, 2, 1, 3)


def _f32(x):
    return np.asarray(x, np.float32)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", sorted(CASES))
def test_forward_matches_contract_reference(case, dtype):
    """o against the bf16-operand reference (the kernel's contract for f32
    inputs at default precision and for bf16 inputs alike)."""
    q, k, v, _ = _inputs(case, dtype)
    o = _kernel(case)(q, k, v)
    o_ref, _ = flash_attention_train_ref(_t(q), _t(k), _t(v),
                                         window=CASES[case][5],
                                         mxu_dtype=jnp.bfloat16)
    assert o.dtype == dtype
    np.testing.assert_allclose(_f32(o), _f32(_t(o_ref)), **TOL[dtype])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", sorted(CASES))
def test_lse_and_backward_match_contract_reference(case, dtype):
    """The log-sum-exp the forward saves, and dq, dk, dv of the custom VJP
    against the reference backward fed the same o and lse."""
    from repro.kernels.flash_attention.kernel import flash_attention_kernel

    window, bq, bk, kvh = CASES[case][5:]
    q, k, v, do = _inputs(case, dtype, seed=1)
    B, S, H, D = q.shape
    merged = lambda x, b: jnp.pad(x.reshape(B, S, -1),
                                  ((0, 0), (0, (-S) % b), (0, 0)))
    o, lse = flash_attention_kernel(merged(q, bq), merged(k, bk),
                                    merged(v, bk), head_dim=D, window=window,
                                    seq_k=S, bq=bq, bk=bk, kv_heads=kvh,
                                    mxu_dtype=jnp.bfloat16, interpret=True)
    o, lse = _t(o[:, :S].reshape(B, S, H, D)), lse[:, :, 0, :S]
    _, lse_ref = flash_attention_train_ref(_t(q), _t(k), _t(v), window=window,
                                           mxu_dtype=jnp.bfloat16)
    np.testing.assert_allclose(_f32(lse), _f32(lse_ref), atol=1e-4,
                               rtol=1e-5)

    out, vjp = jax.vjp(_kernel(case), q, k, v)
    np.testing.assert_array_equal(_f32(out), _f32(_t(o)))
    grads = vjp(do)
    refs = flash_attention_train_bwd_ref(_t(q), _t(k), _t(v), o, lse, _t(do),
                                         window=window,
                                         mxu_dtype=jnp.bfloat16)
    for name, g, r in zip("qkv", grads, refs):
        assert g.dtype == dtype, name
        np.testing.assert_allclose(_f32(g), _f32(_t(r)), **TOL[dtype],
                                   err_msg=f"d{name}")


@pytest.mark.parametrize("case", sorted(CASES))
def test_f32_operands_match_autodiff_of_naive_attention(case):
    """With f32 MXU operands (highest precision) the kernels are exact
    attention: forward and gradients agree with autodiff of the model's
    naive attention, an implementation they share nothing with."""
    q, k, v, do = _inputs(case, jnp.float32, seed=2)
    pos = jnp.arange(q.shape[1])
    naive = lambda q, k, v: naive_attention(q, k, v, pos, pos, CASES[case][5])
    with jax.default_matmul_precision("highest"):
        assert mxu_dtype_for(jnp.float32) == jnp.float32
        o, vjp = jax.vjp(_kernel(case), q, k, v)
        o_ref, vjp_ref = jax.vjp(naive, q, k, v)
        grads, refs = vjp(do), vjp_ref(do)
    np.testing.assert_allclose(_f32(o), _f32(o_ref), atol=2e-5, rtol=2e-5)
    for name, g, r in zip("qkv", grads, refs):
        np.testing.assert_allclose(_f32(g), _f32(r), atol=5e-5, rtol=5e-5,
                                   err_msg=f"d{name}")


# latent attention (models/attention.py::mla_attention): q and k heads
# wider than v heads, 192 and 128 in Moonlight; (B, S, H, D, Dv, bq) with
# one block each way (the single backward kernel) and several (dK/dV, dQ)
QK_V_CASES = {"one_block": (2, 64, 2, 48, 32, 64),
              "blocks": (1, 64, 2, 48, 32, 32)}


@pytest.mark.parametrize("case", sorted(QK_V_CASES))
def test_wider_qk_than_v_heads_match_references(case):
    """Forward, log-sum-exp and gradients at qk width != v width, against
    ref.py's contract reference (bf16 operands) and, with f32 operands,
    autodiff of the model's naive attention."""
    B, S, H, D, Dv, blk = QK_V_CASES[case]
    ks = jax.random.split(jax.random.key(3), 4)
    q, k = (jax.random.normal(kk, (B, S, H, D), jnp.float32) for kk in ks[:2])
    v, do = (jax.random.normal(kk, (B, S, H, Dv), jnp.float32)
             for kk in ks[2:])
    kern = lambda q, k, v: flash_attention_train(
        q, k, v, bq=blk, bk=blk, kv_heads=2, interpret=True)
    o, vjp = jax.vjp(kern, q, k, v)
    assert o.shape == (B, S, H, Dv)
    o_ref, lse = flash_attention_train_ref(_t(q), _t(k), _t(v),
                                           mxu_dtype=jnp.bfloat16)
    np.testing.assert_allclose(_f32(o), _f32(_t(o_ref)), **TOL[jnp.float32])
    refs = flash_attention_train_bwd_ref(_t(q), _t(k), _t(v), o_ref, lse,
                                         _t(do), mxu_dtype=jnp.bfloat16)
    for name, g, r in zip("qkv", vjp(do), refs):
        np.testing.assert_allclose(_f32(g), _f32(_t(r)), **TOL[jnp.float32],
                                   err_msg=f"d{name}")
    pos = jnp.arange(S)
    with jax.default_matmul_precision("highest"):
        o, vjp = jax.vjp(kern, q, k, v)
        o_n, vjp_n = jax.vjp(lambda q, k, v: naive_attention(q, k, v, pos,
                                                             pos), q, k, v)
        grads, refs = vjp(do), vjp_n(do)
    np.testing.assert_allclose(_f32(o), _f32(o_n), atol=2e-5, rtol=2e-5)
    for name, g, r in zip("qkv", grads, refs):
        np.testing.assert_allclose(_f32(g), _f32(r), atol=5e-5, rtol=5e-5,
                                   err_msg=f"d{name}")


def test_mxu_dtype_follows_input_dtype_and_precision():
    assert mxu_dtype_for(jnp.float32) == jnp.bfloat16
    assert mxu_dtype_for(jnp.bfloat16) == jnp.bfloat16
    with jax.default_matmul_precision("highest"):
        assert mxu_dtype_for(jnp.float32) == jnp.float32
        assert mxu_dtype_for(jnp.bfloat16) == jnp.bfloat16


def test_under_vmap_matches_per_client_calls():
    """The client's layer 0 runs vmapped over clients: the batching rule
    adds a grid axis; forward and gradients equal per-client calls."""
    K = 2
    q, k, v, do = (jnp.stack([x] * K) * (1 + jnp.arange(K)).reshape(
        K, 1, 1, 1, 1) / K for x in _inputs("gqa_padded", jnp.float32))
    f = _kernel("gqa_padded")

    def grads(q, k, v, do):
        o, vjp = jax.vjp(f, q, k, v)
        return (o,) + vjp(do)

    got = jax.vmap(grads)(q, k, v, do)
    for c in range(K):
        want = grads(q[c], k[c], v[c], do[c])
        for g, w in zip(got, want):
            np.testing.assert_allclose(_f32(g[c]), _f32(w), atol=1e-6,
                                       rtol=1e-6)


def test_inside_scan_matches_unrolled_layers():
    """A depth scan over layers (the server stack) differentiates through
    the custom VJP exactly as the same layers unrolled."""
    q, k, v, _ = _inputs("mha", jnp.float32, seed=3)
    f = _kernel("mha")
    ws = jnp.stack([jnp.eye(q.shape[-1]) * s for s in (1.0, -0.7)])

    def layer(x, w):
        y = f(x @ w, k, v)
        return x + y, None

    def scanned(x, ws):
        return jnp.sum(jax.lax.scan(layer, x, ws)[0] ** 2)

    def unrolled(x, ws):
        for i in range(ws.shape[0]):
            x, _ = layer(x, ws[i])
        return jnp.sum(x ** 2)

    got = jax.grad(scanned, argnums=(0, 1))(q, ws)
    want = jax.grad(unrolled, argnums=(0, 1))(q, ws)
    for g, w in zip(got, want):
        # f32 rounding of sums over the layers, at the gradients' scale
        np.testing.assert_allclose(_f32(g), _f32(w), rtol=1e-5,
                                   atol=1e-6 * float(jnp.max(jnp.abs(w))))


def _counts():
    return (backend.DISPATCH_COUNTS.get(("flash_attention_train", "kernel"), 0),
            backend.DISPATCH_COUNTS.get(("flash_attention_train", "ref"), 0))


def _as_on_tpu(monkeypatch):
    """The dispatch resolved as on a TPU (the kernels' branch), with the
    kernels in Pallas interpret mode."""
    monkeypatch.setattr(backend, "resolve", lambda interpret, use_kernel:
                        (True, True))


@pytest.mark.parametrize("kv_chunk,q_chunk", [(512, 0), (16, 0), (16, 32)])
def test_run_attention_takes_the_kernel_only_when_asked_off_tpu(
        monkeypatch, kv_chunk, q_chunk):
    """Off-TPU the model keeps today's jnp paths (the degenerate naive
    branch and the online scan), counted as the dispatch's ref branch; a
    dispatch that resolves to the kernels, as on a TPU, takes them and
    counts them."""
    q, k, v, _ = _inputs("gqa", jnp.float32, seed=4)
    pos = jnp.arange(q.shape[1])
    kw = dict(kv_chunk=kv_chunk, q_chunk=q_chunk, causal_prefix=True)
    kern0, ref0 = _counts()
    o = run_attention(q, k, v, pos, pos, **kw)
    assert _counts() == (kern0, ref0 + 1)
    if kv_chunk >= q.shape[1] and not q_chunk:
        np.testing.assert_array_equal(_f32(o),
                                      _f32(naive_attention(q, k, v, pos, pos)))
    _as_on_tpu(monkeypatch)
    o_k = run_attention(q, k, v, pos, pos, **kw)
    assert _counts() == (kern0 + 1, ref0 + 1)
    with jax.default_matmul_precision("highest"):
        o_k = run_attention(q, k, v, pos, pos, **kw)
    np.testing.assert_allclose(_f32(o_k), _f32(o), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("why", ["not_causal_prefix", "s_low_precision",
                                 "cross_lengths", "mixed_dtypes"])
def test_run_attention_keeps_uncovered_calls_on_the_jnp_path(monkeypatch,
                                                             why):
    q, k, v, _ = _inputs("gqa", jnp.float32, seed=5)
    pos = jnp.arange(q.shape[1])
    kw = dict(causal_prefix=why != "not_causal_prefix",
              s_low_precision=why == "s_low_precision", kv_chunk=16)
    kpos = pos
    if why == "cross_lengths":
        k, v, kpos = k[:, :48], v[:, :48], pos[:48]
    if why == "mixed_dtypes":
        v = v.astype(jnp.bfloat16)
    _as_on_tpu(monkeypatch)
    before = _counts()
    run_attention(q, k, v, pos, kpos, **kw)
    assert _counts() == before


def test_train_blocks_fit_and_cover_the_cells():
    """The block rule at both cells' pooled shapes: no padding, several
    heads per step, and tiles the VMEM budget holds; a short sequence is
    one block rounded to the sublane tile."""
    from repro.kernels.flash_attention import tune

    for B, H in ((15, 12), (5, 16)):
        bq, bk, kvh = best_train_blocks(B, H, H, 512, 512, 64, jnp.float32)
        assert 512 % bq == 0 and 512 % bk == 0 and H % kvh == 0
        assert kvh > 1
        assert tune._train_vmem_bytes(bq, bk, kvh, 1, 64, 4) <= \
            tune._VMEM_BUDGET
    assert best_train_blocks(2, 4, 2, 5, 5, 32, jnp.float32)[:2] == (8, 8)
    assert best_train_blocks(2, 4, 2, 5, 5, 32, jnp.bfloat16)[:2] == (16, 16)
    assert best_train_blocks(2, 4, 2, 200, 200, 32)[:2] == (256, 256)
