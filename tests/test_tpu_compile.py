"""Ahead-of-time compiles of the main-path Pallas kernels for a described
TPU v5e chip, at GPT2-S widths.

Nothing runs: each test lowers one kernel entry from shapes alone and
compiles it with the TPU compiler for a chip that is described, not
attached, so a block layout or VMEM budget the chip's compiler refuses
fails here instead of on the chip.  Interpret-mode parity tests cannot
catch those refusals.  The topology is described inside a module fixture,
never at import, so only the worker that runs this file loads the TPU
library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import (flash_attention_train,
                                           flash_decode, paged_decode)
from repro.kernels.lora_matmul import lora_matmul, lora_matmul_gathered

# GPT2-S (configs/gpt2_s.py): d 768, d_ff 3072, 12 heads of 64, LoRA r 4.
# Training projections see M = batch x seq = 4 x 512 rows per client;
# serving decodes 4 slots over a 128-token cache in 16-token pages.
D_MODEL, D_FF, HEADS, HEAD_DIM, RANK = 768, 3072, 12, 64, 4
M_TRAIN, SLOTS, MAX_LEN, PAGE = 4 * 512, 4, 128, 16
# the benchmark cells' pooled server batch K x b at S = 512, f32: GPT2-S
# (K 5 x b 3, 12 heads of 64) and GPT2-M (5 x 1, 16 heads of 64), where
# the backward is one kernel; and a longer sequence of several blocks,
# where it is a dK/dV and a dQ kernel
ATTN_CELLS = {"gpt2-s": (15, 512, 12, 64), "gpt2-m": (5, 512, 16, 64),
              "gpt2-s-s2048": (2, 2048, 12, 64)}
ATTN_BWD = {"gpt2-s": ("attn_bwd",), "gpt2-m": ("attn_bwd",),
            "gpt2-s-s2048": ("attn_dkv", "attn_dq")}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_cache():
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_lora_matmul_forward_compiles(one_chip, no_cache, dtype):
    def fwd(x, w, a, b):
        return lora_matmul(x, w, a, b, scale=2.0, interpret=False)

    _compile(fwd, [((M_TRAIN, D_MODEL), dtype), ((D_MODEL, D_FF), dtype),
                   ((RANK, D_MODEL), dtype), ((D_FF, RANK), dtype)], one_chip)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_lora_matmul_backward_compiles(one_chip, no_cache, dtype):
    def loss(x, w, a, b):
        y = lora_matmul(x, w, a, b, scale=2.0, interpret=False)
        return jnp.sum(y.astype(jnp.float32))

    grad = jax.grad(loss, argnums=(0, 2, 3))
    _compile(grad, [((M_TRAIN, D_FF), dtype), ((D_FF, D_MODEL), dtype),
                    ((RANK, D_FF), dtype), ((D_MODEL, RANK), dtype)],
             one_chip)


def test_lora_matmul_gathered_compiles(one_chip, no_cache):
    pool = 3

    def gathered(x, w, a, b, idx):
        return lora_matmul_gathered(x, w, a, b, idx, scale=2.0,
                                    interpret=False)

    _compile(gathered, [((SLOTS, D_MODEL), jnp.float32),
                        ((D_MODEL, D_FF), jnp.float32),
                        ((pool, RANK, D_MODEL), jnp.float32),
                        ((pool, D_FF, RANK), jnp.float32),
                        ((SLOTS,), jnp.int32)], one_chip)


def test_flash_decode_compiles(one_chip, no_cache):
    def decode(q, k, v, lengths):
        return flash_decode(q, k, v, lengths, interpret=False)

    _compile(decode, [((SLOTS, 1, HEADS, HEAD_DIM), jnp.float32),
                      ((SLOTS, MAX_LEN, HEADS, HEAD_DIM), jnp.float32),
                      ((SLOTS, MAX_LEN, HEADS, HEAD_DIM), jnp.float32),
                      ((SLOTS,), jnp.int32)], one_chip)


@pytest.mark.parametrize("kv_dtype", [jnp.float32, jnp.int8])
def test_paged_decode_compiles(one_chip, no_cache, kv_dtype):
    pages = SLOTS * (MAX_LEN // PAGE) + 1
    shapes = [((SLOTS, 1, HEADS, HEAD_DIM), jnp.float32),
              ((HEADS, pages, PAGE, HEAD_DIM), kv_dtype),
              ((HEADS, pages, PAGE, HEAD_DIM), kv_dtype),
              ((SLOTS,), jnp.int32),
              ((SLOTS, MAX_LEN // PAGE), jnp.int32)]
    if kv_dtype == jnp.int8:
        shapes += [((HEADS,), jnp.float32), ((HEADS,), jnp.float32)]

        def decode(q, kp, vp, lengths, bt, ks, vs):
            return paged_decode(q, kp, vp, lengths, bt, k_scale=ks,
                                v_scale=vs, interpret=False)
    else:
        def decode(q, kp, vp, lengths, bt):
            return paged_decode(q, kp, vp, lengths, bt, interpret=False)

    _compile(decode, shapes, one_chip)


def _named(text, scope):
    """Custom calls whose instruction name carries ``scope``."""
    import re
    return re.findall(rf"^\s*(?:ROOT )?%?[\w.-]*{scope}[\w.-]* = .* "
                      r"custom-call\(", text, re.M)


@pytest.mark.parametrize("cell", sorted(ATTN_CELLS))
def test_flash_attention_train_forward_compiles(one_chip, no_cache, cell):
    shape = ATTN_CELLS[cell]

    def fwd(q, k, v):
        return flash_attention_train(q, k, v, interpret=False)

    text = _compile(fwd, [(shape, jnp.float32)] * 3, one_chip)
    assert len(_named(text, "attn_fwd")) == 1


@pytest.mark.parametrize("cell", sorted(ATTN_CELLS))
def test_flash_attention_train_backward_compiles(one_chip, no_cache, cell):
    shape = ATTN_CELLS[cell]

    def loss(q, k, v, g):
        return jnp.sum(flash_attention_train(q, k, v, interpret=False) * g)

    text = _compile(jax.grad(loss, argnums=(0, 1, 2)),
                    [(shape, jnp.float32)] * 4, one_chip)
    for scope in ("attn_fwd",) + ATTN_BWD[cell]:
        assert len(_named(text, scope)) == 1, scope
    for scope in {"attn_bwd", "attn_dkv", "attn_dq"} - set(ATTN_BWD[cell]):
        assert not _named(text, scope), scope


def test_attention_kernels_named_beside_lora_in_a_scanned_grad(one_chip,
                                                               no_cache):
    """A gradient through a depth scan of one LoRA projection and the
    attention custom VJP: each attention kernel's instruction (forward,
    one-block backward) carries its scope's name, so ``closed_call``
    counts the LoRA kernels alone (the forward, dX and two rank
    reductions), as the benchmark's ``lora_roofline.train`` reads them."""
    L, B, S, H, D = 2, 2, 128, 2, 64
    d = H * D

    def loss(x, w, a, b):
        def layer(x, p):
            h = lora_matmul(x, *p, scale=2.0, interpret=False)
            o = flash_attention_train(*(h.reshape(B, S, H, D),) * 3,
                                      interpret=False)
            return x + o.reshape(B, S, d), None
        return jnp.sum(jax.lax.scan(layer, x, (w, a, b))[0] ** 2)

    text = _compile(jax.grad(loss, argnums=(0, 2, 3)),
                    [((B, S, d), jnp.float32), ((L, d, d), jnp.float32),
                     ((L, RANK, d), jnp.float32), ((L, d, RANK), jnp.float32)],
                    one_chip)
    assert len(_named(text, "closed_call")) == 4
    for scope in ("attn_fwd", "attn_bwd"):
        assert len(_named(text, scope)) == 1, scope


def test_sfl_round_keeps_kernel_names_under_phase_scopes(one_chip, no_cache,
                                                         monkeypatch):
    """The tiny SFL round with the fused kernels forced on: the phase
    scopes sit above the kernels, so each is still an instruction named
    ``closed_call`` (a scope directly around one would rename it, and the
    benchmark counts kernels by that name), and the phases reach the
    compiled fusions' metadata.  Per local step of a 2-layer round split
    at 1: 2L forward, 2(L-1) dX and 4L rank reductions.  The attention
    kernels sit in scopes of their own and carry those names: one
    instruction each for the client's vmapped layer and the server's
    depth scan."""
    import re

    from repro.configs import TrainConfig, get_arch
    from repro.core.sfl import SflLLM
    from repro.kernels import backend
    from repro.models import layers
    from repro.models.model import init_lora_stack, init_params
    from repro.optim import adamw

    monkeypatch.setattr(layers, "FUSED_DENSE_BACKENDS", ("tpu", "cpu"))
    monkeypatch.setattr(backend, "auto_interpret", lambda: False)
    L, K, b, S, steps = 2, 2, 2, 128, 2
    cfg = get_arch("gpt2-s").reduced(num_layers=L, d_model=128, vocab=512)
    sfl = SflLLM(cfg, init_params(cfg, jax.random.key(0)), ell_c=1,
                 train_cfg=TrainConfig(num_clients=K, batch_size=b,
                                       local_steps=steps),
                 optimizer=adamw(1e-3))
    state = sfl.init_state(init_lora_stack(cfg, jax.random.key(1)))

    def shaped(tree):
        return jax.tree.map(lambda v: jax.ShapeDtypeStruct(
            v.shape, v.dtype, sharding=one_chip), tree)

    ids = jax.ShapeDtypeStruct((steps, K, b, S), jnp.int32,
                               sharding=one_chip)
    per_client = jax.ShapeDtypeStruct((K,), jnp.float32, sharding=one_chip)
    text = sfl._jit_round_part.lower(
        shaped(sfl.base), shaped(state), {"tokens": ids, "labels": ids},
        per_client, per_client, None).compile().as_text()
    kernels = re.findall(r"^\s*(?:ROOT )?%?closed_call[.\d]* = .* "
                         r"custom-call\(", text, re.M)
    assert len(kernels) == 2 * L + 2 * (L - 1) + 4 * L
    for scope in ("attn_fwd", "attn_bwd"):
        assert len(_named(text, scope)) == 2, scope
    fusion_meta = "\n".join(re.findall(r"^\s*(?:ROOT )?%?[\w.-]*fusion[\w.-]* = "
                                       r".*op_name=\"([^\"]+)\"", text, re.M))
    for phase in ("sfl.server_stack", "sfl.client"):
        assert phase in fusion_meta


# latent attention at a Moonlight SFL round's pooled server batch (K=5
# clients x b=1 x S=2048): 16 heads, q and k 192 wide, v 128 (several
# blocks: dK/dV and dQ)
MLA_CELL = (5, 2048, 16, 192, 128)


def test_mla_attention_kernels_compile(one_chip, no_cache):
    B, S, H, D, Dv = MLA_CELL

    def loss(q, k, v, g):
        return jnp.sum(flash_attention_train(q, k, v, interpret=False) * g)

    qk, vs = ((B, S, H, D), jnp.float32), ((B, S, H, Dv), jnp.float32)
    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), [qk, qk, vs, vs],
                    one_chip)
    for scope in ("attn_fwd", "attn_dkv", "attn_dq"):
        assert len(_named(text, scope)) == 1, scope


def test_grouped_matmul_kernels_compile(one_chip, no_cache):
    """The held experts' SwiGLU at a Moonlight round's shapes (10,240
    tokens x top-6 rows, 8 experts of 2048 -> 1408), forward and dX: each
    kernel is an instruction named after its scope, none ``closed_call``
    (which the LoRA kernels' reader counts)."""
    from repro.kernels.grouped_matmul import expert_swiglu

    M, d, f, E = 10240 * 6, 2048, 1408, 8

    def loss(xs, gate, gs, wg, wu, wd):
        y = expert_swiglu(xs, gate, gs, wg, wu, wd, interpret=False)
        return jnp.sum(y * y)

    text = _compile(jax.grad(loss, argnums=(0, 1)),
                    [((M, d), jnp.float32), ((M,), jnp.float32),
                     ((E + 1,), jnp.int32), ((E, d, f), jnp.float32),
                     ((E, d, f), jnp.float32), ((E, f, d), jnp.float32)],
                    one_chip)
    # gate, up and down, and again from the kept rows in the backward
    assert len(_named(text, "moe_gmm_fwd")) == 6
    assert len(_named(text, "moe_gmm_dx")) == 3
    assert not _named(text, "closed_call")


def test_gpt2_cells_keep_their_attention_blocks_and_traces(one_chip,
                                                           no_cache,
                                                           monkeypatch):
    """Separate q/k and v widths leave GPT-2's attention as it was: the
    block rule still picks (512, 512, 2 KV heads) at both cells' pooled
    shapes, and a round traces each kernel body once per shape: the
    forward and the one-block backward, for the client's vmapped call and
    the server's scan (4 traces)."""
    from repro.configs import TrainConfig, get_arch
    from repro.core.sfl import SflLLM
    from repro.kernels import backend
    from repro.kernels.flash_attention import best_train_blocks, kernel
    from repro.models.model import init_lora_stack, init_params
    from repro.optim import adamw

    for B, H in ((15, 12), (5, 16)):
        assert best_train_blocks(B, H, H, 512, 512, 64) == (512, 512, 2)
    monkeypatch.setattr(backend, "auto_interpret", lambda: False)
    monkeypatch.setattr(kernel, "_TRACED", {})
    cfg = get_arch("gpt2-s").reduced(num_layers=2, d_model=128, vocab=512)
    sfl = SflLLM(cfg, init_params(cfg, jax.random.key(0)), ell_c=1,
                 train_cfg=TrainConfig(num_clients=2, batch_size=2,
                                       local_steps=2),
                 optimizer=adamw(1e-3))
    state = sfl.init_state(init_lora_stack(cfg, jax.random.key(1)))
    shaped = lambda t: jax.tree.map(lambda v: jax.ShapeDtypeStruct(
        v.shape, v.dtype, sharding=one_chip), t)
    ids = jax.ShapeDtypeStruct((2, 2, 2, 128), jnp.int32, sharding=one_chip)
    pc = jax.ShapeDtypeStruct((2,), jnp.float32, sharding=one_chip)
    sfl._jit_round_part.lower(shaped(sfl.base), shaped(state),
                              {"tokens": ids, "labels": ids}, pc, pc, None)
    assert sorted(k[0] for k in kernel._TRACED) == ["attn_bwd", "attn_bwd",
                                                    "attn_fwd", "attn_fwd"]


def test_gelu_mlp_stack_saves_one_term_a_layer(one_chip, no_cache):
    """Gradient through a 2-layer depth scan of ``gelu_mlp`` at GPT2-S
    widths and the S cell's pooled server rows (K 5 x b 3 x S 512), the
    weights frozen: the scan saves one f32 ``[T, d_ff]`` term a layer (the
    pre-activation), where autodiff of the tanh GELU saved five."""
    from repro.configs import get_arch
    from repro.models import layers

    cfg = get_arch("gpt2-s")
    L, T = 2, 15 * 512

    def loss(x, p):
        def body(x, p):
            return x + layers.gelu_mlp(cfg, x, p), None
        return jnp.sum(jax.lax.scan(body, x, p)[0] ** 2)

    sd = lambda s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
    p = {"w_up": {"w": sd((L, D_MODEL, D_FF)), "b": sd((L, D_FF))},
         "w_down": {"w": sd((L, D_FF, D_MODEL)), "b": sd((L, D_MODEL))}}
    mem = jax.jit(jax.grad(loss)).lower(sd((T, D_MODEL)), p).compile(
    ).memory_analysis()
    term = T * D_FF * 4
    assert mem.temp_size_in_bytes < 2 * L * term
