"""``layers.gelu_new``: GPT-2's tanh GELU behind a custom VJP that keeps
only the pre-activation.  Its values and gradients match plain autodiff
of ``jax.nn.gelu(approximate=True)``, and ``gelu_mlp``'s backward holds
one ``[T, d_ff]`` array where autodiff held five."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.models import layers

# 0, tiny, moderate, and |x| far enough out that tanh saturates
EDGES = [0.0, 1e-3, -1e-3, 0.5, -0.5, 3.0, -3.0, 10.0, -10.0, 20.0, -20.0,
         100.0, -100.0]


def _autodiff(h):
    return jax.nn.gelu(h.astype(jnp.float32), approximate=True).astype(h.dtype)


def _inputs(dtype):
    k1, k2 = jax.random.split(jax.random.key(0))
    rand = 3.0 * jax.random.normal(k1, (4 * 32 - len(EDGES),))
    h = jnp.concatenate([jnp.asarray(EDGES), rand]).reshape(4, 32)
    return h.astype(dtype), jax.random.normal(k2, (4, 32)).astype(dtype)


def _wrap(f, how):
    if how == "vmap":
        return jax.vmap(f)
    if how == "scan":
        return lambda h: jax.lax.scan(lambda c, r: (c, f(r)), 0.0, h)[1]
    return f


@pytest.mark.parametrize("how", ["plain", "vmap", "scan"])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-6),
                                       (jnp.bfloat16, 1e-2)])
def test_gelu_new_matches_autodiff(dtype, tol, how):
    h, g = _inputs(dtype)
    y, vjp = jax.vjp(_wrap(layers.gelu_new, how), h)
    y_ref, vjp_ref = jax.vjp(_wrap(_autodiff, how), h)
    assert y.dtype == dtype
    np.testing.assert_array_equal(np.asarray(y, np.float32),
                                  np.asarray(y_ref, np.float32))
    (dh,), (dh_ref,) = vjp(g), vjp_ref(g)
    assert dh.dtype == dtype
    np.testing.assert_allclose(np.asarray(dh, np.float32),
                               np.asarray(dh_ref, np.float32),
                               rtol=tol, atol=tol)
    # jax.grad through the same wrapper agrees too
    grad = jax.grad(lambda h: jnp.sum(_wrap(layers.gelu_new, how)(h)
                                      .astype(jnp.float32)))(h)
    grad_ref = jax.grad(lambda h: jnp.sum(_wrap(_autodiff, how)(h)
                                          .astype(jnp.float32)))(h)
    np.testing.assert_allclose(np.asarray(grad, np.float32),
                               np.asarray(grad_ref, np.float32),
                               rtol=tol, atol=tol)


def test_gelu_mlp_backward_keeps_only_the_preactivation():
    """Frozen weights (closed over), gradient to x only: the VJP's
    residuals hold the pre-activation and the two weights, no other
    ``[T, d_ff]`` array."""
    cfg = get_arch("gpt2-s").reduced(num_layers=2, d_model=64, vocab=128)
    p = layers.init_mlp(cfg, jax.random.key(0), jnp.float32)
    B, S = 2, 24
    x = jax.random.normal(jax.random.key(1), (B, S, cfg.d_model))
    _, vjp = jax.vjp(lambda x: layers.gelu_mlp(cfg, x, p), x)
    saved = [leaf for leaf in jax.tree.leaves(vjp)
             if getattr(leaf, "shape", ()) == (B, S, cfg.d_ff)]
    assert len(saved) == 1
