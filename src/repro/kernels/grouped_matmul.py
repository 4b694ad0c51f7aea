"""Grouped matmuls over the experts a chip holds: one SwiGLU expert FFN per
group of rows, forward and dX behind one custom VJP.

Rows come sorted by expert.  ``group_sizes`` has one entry per held expert
and a last one for the rows no held expert takes; those rows come out 0.
On a TPU each product is a ``megablox`` grouped-matmul kernel (shipped
with JAX) that visits only the tiles of the held experts' rows, each in a
named scope (``moe_gmm_fwd``, ``moe_gmm_dx``) that names its instruction
in the compiled program; elsewhere each expert's product is masked to its
rows (small shapes: CPU tests).  Each row's output is scaled by its gate
weight inside, so the backward needs no copy of the rows' outputs.  The
expert weights are frozen, so the backward makes dX only.  It keeps the
rows alone, in the kernels' operand dtype (bfloat16 for float32 rows: a
third of the bytes of the float32 gate and up projections at the
worst case's row count), and makes the projections again from them: the
same kernels on the same operands, so the same numbers.
"""
from __future__ import annotations

import functools
import importlib
from typing import NamedTuple

import jax
import jax.numpy as jnp

from . import backend
from .flash_attention.ops import mxu_dtype_for

# the kernel module itself; the package's ``gmm`` is its own custom VJP,
# which would make dW
_megablox = importlib.import_module(
    "jax.experimental.pallas.ops.tpu.megablox.gmm")

SCOPES = ("moe_gmm_fwd", "moe_gmm_dx")


def gmm_tiles(k: int, n: int) -> tuple:
    """(rows, contraction, output) tile of one grouped matmul: 512 rows, the
    whole output width where it is at most 1536 lanes (each row tile then
    reads its rows once), else 512-wide output tiles over the whole
    contraction (up to 1536); under 12 MB of VMEM with double buffering
    at bf16 operands."""
    if n <= 1536:
        return 512, min(k, 512), n
    return 512, min(k, 1536), 512


class _Cfg(NamedTuple):
    kernel: bool
    interpret: bool
    mxu_dtype: str


def _mm(cfg: _Cfg, scope: str, x, w, group_sizes, transpose_rhs=False):
    """x (M, k) by the held experts' w (E, k, n), or (E, n, k) transposed:
    each group of rows by its expert's matrix, f32 out."""
    if not cfg.kernel:
        # each expert's matrix over every row, kept on its own rows: E
        # times the work, and unlike ragged_dot it batches under vmap
        wt = jnp.swapaxes(w, 1, 2) if transpose_rhs else w
        ends = jnp.cumsum(group_sizes)
        row = jnp.arange(x.shape[0])[:, None]
        y = jnp.zeros((x.shape[0], wt.shape[2]), jnp.float32)
        for e in range(wt.shape[0]):
            mine = (row >= ends[e] - group_sizes[e]) & (row < ends[e])
            y = y + jnp.where(mine, jnp.dot(
                x, wt[e].astype(x.dtype), preferred_element_type=jnp.float32),
                0.0)
        return y
    dt = jnp.dtype(cfg.mxu_dtype)
    M, k = x.shape
    n = w.shape[1] if transpose_rhs else w.shape[2]
    tiles = gmm_tiles(k, n)
    pad = (-M) % tiles[0]            # rows past every group come out 0
    xp = jnp.pad(x, ((0, pad), (0, 0))) if pad else x
    with jax.named_scope(scope):
        # the unjitted body: a jitted one would name the instruction "gmm"
        y = _megablox.gmm.__wrapped__(
            xp.astype(dt), w.astype(dt), group_sizes,
            preferred_element_type=jnp.float32, tiling=tiles,
            transpose_rhs=transpose_rhs, interpret=cfg.interpret)
    return y[:M] if pad else y


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _ffn(cfg: _Cfg, xs, gate, group_sizes, w_gate, w_up, w_down):
    return _ffn_fwd(cfg, xs, gate, group_sizes, w_gate, w_up, w_down)[0]


def _ffn_fwd(cfg, xs, gate, group_sizes, w_gate, w_up, w_down):
    if cfg.kernel:
        xs = xs.astype(cfg.mxu_dtype)          # what the kernels read
    g = _mm(cfg, SCOPES[0], xs, w_gate, group_sizes)
    u = _mm(cfg, SCOPES[0], xs, w_up, group_sizes)
    y = _mm(cfg, SCOPES[0], jax.nn.silu(g) * u, w_down, group_sizes)
    return y * gate[:, None], (xs, gate, group_sizes, w_gate, w_up, w_down)


def _ffn_bwd(cfg, res, dy):
    """dX, and the gate weights' gradient, from the gate and up
    projections and the rows' outputs made again from the kept rows
    (three more forward products) rather than kept: at the worst case's
    row count they would be the layer's largest residuals."""
    xs, gate, group_sizes, w_gate, w_up, w_down = res
    g = _mm(cfg, SCOPES[0], xs, w_gate, group_sizes)
    u = _mm(cfg, SCOPES[0], xs, w_up, group_sizes)
    sg = jax.nn.sigmoid(g)
    y = _mm(cfg, SCOPES[0], g * sg * u, w_down, group_sizes)
    dgate = jnp.sum(dy * y, axis=-1)
    dh = _mm(cfg, SCOPES[1], dy * gate[:, None], w_down, group_sizes,
             transpose_rhs=True)
    dg = dh * u * sg * (1.0 + g * (1.0 - sg))
    du = dh * g * sg
    dx = (_mm(cfg, SCOPES[1], dg, w_gate, group_sizes, transpose_rhs=True)
          + _mm(cfg, SCOPES[1], du, w_up, group_sizes, transpose_rhs=True))
    return dx, dgate, None, None, None, None


_ffn.defvjp(_ffn_fwd, _ffn_bwd)


def expert_swiglu(xs, gate, group_sizes, w_gate, w_up, w_down, *,
                  interpret: "bool | None" = None,
                  use_kernel: "bool | None" = None):
    """SwiGLU of each held expert over its rows, times each row's gate
    weight: xs (M, d) sorted by expert; gate (M,) f32; group_sizes (E + 1,)
    int32, the last entry the rows no held expert takes; w_gate, w_up
    (E, d, f), w_down (E, f, d).  Returns (M, d) f32, 0 on rows past the
    held experts'.  Differentiable in xs and gate.  Dispatch as
    ``kernels.backend``: the kernels on a TPU, the masked products
    elsewhere, an explicit ``interpret`` forces the kernels."""
    interpret, use_kernel = backend.resolve(interpret, use_kernel)
    branch = "kernel" if use_kernel else "ref"
    backend.DISPATCH_COUNTS[("expert_swiglu", branch)] = \
        backend.DISPATCH_COUNTS.get(("expert_swiglu", branch), 0) + 1
    cfg = _Cfg(use_kernel, interpret, mxu_dtype_for(xs.dtype).name)
    return _ffn(cfg, xs, gate.astype(jnp.float32),
                group_sizes.astype(jnp.int32), w_gate, w_up, w_down)
