"""Decoder blocks + `lax.scan`-over-depth stacks.

Parameters for the repeating depth pattern are stored as a tuple (one entry
per pattern position) of block pytrees whose leaves are stacked over the
``pattern_repeats`` axis — HLO size and compile time are then independent
of depth (88-layer Mistral-Large compiles as one scan).  The SFL split
point slices this stacked axis (``core/split.py``).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from ..precision import PrecisionConfig
from . import attention as attn_mod
from . import moe as moe_mod
from . import ssm as ssm_mod
from .layers import _cast_like, apply_mlp, apply_norm, init_mlp, init_norm


@dataclass(frozen=True)
class Runtime:
    """Runtime/perf knobs (hillclimb levers), orthogonal to ArchConfig."""

    attn_impl: str = "chunked"          # "naive" | "chunked"
    dense_impl: str = "einsum"          # "einsum" | "fused" (kernels.lora_matmul)
    kv_chunk: int = 512
    q_chunk: int = 0                    # 0 = no query blocking
    # "naive" keeps the whole (B,H,1,L) score einsum (GSPMD shards the
    # cache seq dim); "flash" routes decode through the split-K
    # kernels.flash_attention.flash_decode dispatch (Pallas on TPU with
    # per-slot live-length tile skipping, same masked einsum elsewhere)
    decode_attn_impl: str = "naive"
    moe_group: int = 128
    capacity_factor: float = 1.25
    remat: bool = False                 # checkpoint each scan body (train)
    remat_policy: str = "full"          # "full" | "dots" (save matmul outs)
    # activation sharding constraints (mesh axis names); () = no constraint.
    # Requires an ambient mesh (jax.sharding.set_mesh) during trace.
    dp_axes: Tuple[str, ...] = ()
    tp_axis: Optional[str] = None
    # beyond-paper perf levers (EXPERIMENTS.md §Perf):
    seq_shard: bool = False             # Megatron-style sequence parallelism
    moe_constraints: bool = False       # explicit dispatch/combine shardings
    attn_s_bf16: bool = False           # bf16 score einsum (uneven-GQA fix)
    # precision as a first-class resource (repro.precision): boundary
    # activation/gradient bit-widths, weight-only int8 base weights,
    # stochastic rounding + error feedback — one typed config instead of
    # per-callsite booleans.  The default is fully disarmed (16/16/f32):
    # bit-identical to a Runtime without the field.
    precision: PrecisionConfig = PrecisionConfig()

    def replace(self, **kw) -> "Runtime":
        import dataclasses
        return dataclasses.replace(self, **kw)


def default_train_runtime() -> Runtime:
    """The trainers' fast-path defaults: chunked online-softmax attention
    (never materializes the S x S score matrix), every LoRA-adapted
    projection through the fused ``kernels.lora_matmul`` dispatch, and the
    cheap "dots" policy if rematerialization is switched on."""
    return Runtime(attn_impl="chunked", dense_impl="fused",
                   remat_policy="dots")


def default_serve_runtime() -> Runtime:
    """The serving fast path: chunked prefill attention, fused LoRA
    projections, and flash-decode — every knob backend-dispatched, so on
    CPU it degenerates to the exact einsum forms."""
    return Runtime(attn_impl="chunked", dense_impl="fused",
                   decode_attn_impl="flash")


# ---------------------------------------------------------------------------
# single block
# ---------------------------------------------------------------------------

def init_block(cfg, pat, key, dtype) -> dict:
    ks = jax.random.split(key, 4)
    p: dict = {"norm1": init_norm(cfg, cfg.d_model, dtype)}
    if pat.mixer == "attention":
        p["mixer"] = attn_mod.init_attention(cfg, ks[0], dtype)
    elif pat.mixer == "mla":
        p["mixer"] = attn_mod.init_mla(cfg, ks[0], dtype)
    else:
        p["mixer"] = ssm_mod.init_mamba(cfg, ks[0], dtype)
    if pat.mlp != "none":
        p["norm2"] = init_norm(cfg, cfg.d_model, dtype)
        if pat.mlp == "dense":
            p["mlp"] = init_mlp(cfg, ks[1], dtype)
        elif cfg.moe_dispatch == "dropless":
            p["mlp"] = moe_mod.init_moe_dropless(cfg, ks[1], dtype)
        else:
            p["mlp"] = moe_mod.init_moe(cfg, ks[1], dtype)
    return p


def _mixer_lora(lora):
    if lora is None:
        return None
    return lora.get("mixer")


def apply_block(cfg, pat, p: dict, x, *, positions, lora, lora_scale, rt: Runtime,
                mode: str, cache=None, cur_index=None, cache_len: int = 0,
                block_tables=None, adapter_idx=None):
    """mode: "train" | "prefill" | "decode" | "chunk".  Returns
    (x, cache_out, aux); in mode "train", which keeps no cache, the second
    value is a dropless MoE layer's count of the rows routed to each
    expert it holds, else None.  ``block_tables`` switches decode onto the
    paged KV pool ((B, MP) page ids; cache is then the (KH, NP, PS, D) pool);
    mode "chunk" is one paged-prefill chunk (block_tables (MP,), cur_index
    the chunk's absolute start).  ``adapter_idx`` (decode only) makes the
    LoRA leaves (A, ...) pools with per-slot adapter selection
    (multi-tenant serving; see ``layers.dense``)."""
    aux = jnp.zeros((), jnp.float32)
    h = apply_norm(cfg, x, p["norm1"])
    cache_out = cache
    if pat.mixer == "mla":
        if mode != "train":
            raise NotImplementedError("latent attention trains only: it "
                                      "has no decode cache yet")
        m = attn_mod.mla_attention(
            cfg, p["mixer"], h, positions, lora=_mixer_lora(lora),
            lora_scale=lora_scale, impl=rt.attn_impl, kv_chunk=rt.kv_chunk,
            q_chunk=rt.q_chunk, dense_impl=rt.dense_impl)
    elif pat.mixer == "attention":
        if mode == "decode" and block_tables is not None:
            m, cache_out = attn_mod.paged_decode_attention(
                cfg, p["mixer"], h, cache, block_tables, cur_index,
                lora=_mixer_lora(lora), lora_scale=lora_scale,
                impl=rt.decode_attn_impl, dense_impl=rt.dense_impl,
                adapter_idx=adapter_idx)
        elif mode == "chunk":
            m, cache_out = attn_mod.paged_chunk_attention(
                cfg, p["mixer"], h, cache, block_tables, cur_index,
                lora=_mixer_lora(lora), lora_scale=lora_scale,
                dense_impl=rt.dense_impl)
        elif mode == "decode":
            m, cache_out = attn_mod.decode_attention(
                cfg, p["mixer"], h, cache, cur_index,
                lora=_mixer_lora(lora), lora_scale=lora_scale,
                impl=rt.decode_attn_impl, dense_impl=rt.dense_impl,
                adapter_idx=adapter_idx)
        elif mode == "prefill":
            m, cache_out = attn_mod.self_attention(
                cfg, p["mixer"], h, positions, lora=_mixer_lora(lora),
                lora_scale=lora_scale, impl=rt.attn_impl, kv_chunk=rt.kv_chunk,
                q_chunk=rt.q_chunk, return_cache=True,
                cache_len=cache["k"].shape[1] if cache is not None else cache_len,
                s_low_precision=rt.attn_s_bf16, dense_impl=rt.dense_impl)
        else:
            m = attn_mod.self_attention(
                cfg, p["mixer"], h, positions, lora=_mixer_lora(lora),
                lora_scale=lora_scale, impl=rt.attn_impl, kv_chunk=rt.kv_chunk,
                q_chunk=rt.q_chunk, s_low_precision=rt.attn_s_bf16,
                dense_impl=rt.dense_impl)
    else:  # mamba
        if mode == "chunk":
            raise NotImplementedError(
                "paged chunk prefill is attention-only (mamba state is not "
                "paged); init_paged_stack_cache rejects such patterns")
        if mode == "decode":
            m, cache_out = ssm_mod.mamba_step(
                cfg, p["mixer"], h, cache, lora=_mixer_lora(lora),
                lora_scale=lora_scale, dense_impl=rt.dense_impl)
        elif mode == "prefill":
            m, cache_out = ssm_mod.mamba_block(
                cfg, p["mixer"], h, lora=_mixer_lora(lora),
                lora_scale=lora_scale, return_state=True,
                dense_impl=rt.dense_impl)
        else:
            m = ssm_mod.mamba_block(cfg, p["mixer"], h,
                                    lora=_mixer_lora(lora),
                                    lora_scale=lora_scale,
                                    dense_impl=rt.dense_impl)
    x = x + m
    if pat.mlp != "none":
        h = apply_norm(cfg, x, p["norm2"])
        if pat.mlp == "moe" and cfg.moe_dispatch == "dropless":
            mo, cache_out = moe_mod.apply_moe_dropless(cfg, p["mlp"], h)
        elif pat.mlp == "moe":
            specs = ((rt.dp_axes, rt.tp_axis)
                     if rt.moe_constraints and rt.dp_axes else None)
            mo, aux = moe_mod.apply_moe(cfg, p["mlp"], h,
                                        group_size=rt.moe_group,
                                        capacity_factor=rt.capacity_factor,
                                        shard_specs=specs)
        else:
            mo = apply_mlp(cfg, h, p["mlp"],
                           None if lora is None else lora.get("mlp"),
                           lora_scale, dense_impl=rt.dense_impl,
                           adapter_idx=adapter_idx)
        x = x + mo
    return x, cache_out, aux


# ---------------------------------------------------------------------------
# stack init
# ---------------------------------------------------------------------------

def init_stack(cfg, key, dtype, pattern=None) -> Tuple[dict, ...]:
    """tuple over pattern positions; leaves stacked over repeats.
    ``pattern=cfg.prefix`` makes the leading layers: one repeat of them."""
    pattern = cfg.pattern if pattern is None else pattern
    P = len(pattern)
    R = cfg.pattern_repeats if pattern is cfg.pattern else 1
    keys = jax.random.split(key, P * R).reshape(P, R)
    out = []
    for pi, pat in enumerate(pattern):
        per_rep = [init_block(cfg, pat, keys[pi, ri], dtype) for ri in range(R)]
        out.append(jax.tree.map(lambda *xs: jnp.stack(xs), *per_rep))
    return tuple(out)


def init_stack_cache(cfg, batch: int, cache_len: int, dtype) -> Tuple[Any, ...]:
    """Decode caches, stacked over repeats, tuple over pattern positions."""
    R = cfg.pattern_repeats
    out = []
    for pat in cfg.pattern:
        if pat.mixer == "attention":
            one = attn_mod.init_attn_cache(cfg, batch, cache_len, dtype)
        else:
            one = ssm_mod.init_mamba_cache(cfg, batch, dtype)
        out.append(jax.tree.map(lambda x: jnp.broadcast_to(x, (R,) + x.shape), one))
    return tuple(out)


def init_paged_stack_cache(cfg, num_pages: int, page_size: int,
                           dtype) -> Tuple[Any, ...]:
    """Paged KV pools, stacked over repeats, tuple over pattern positions.
    Attention-only: mamba state has no length axis to page."""
    if any(pat.mixer != "attention" for pat in cfg.pattern):
        raise NotImplementedError(
            "paged KV cache requires an attention-only pattern")
    R = cfg.pattern_repeats
    out = []
    for _ in cfg.pattern:
        one = attn_mod.init_paged_attn_cache(cfg, num_pages, page_size, dtype)
        out.append(jax.tree.map(lambda x: jnp.broadcast_to(x, (R,) + x.shape), one))
    return tuple(out)


# ---------------------------------------------------------------------------
# stack apply (scan over repeats)
# ---------------------------------------------------------------------------

def apply_stack(cfg, stack_params, x, *, positions, lora=None, rt: Runtime,
                mode: str = "train", caches=None, cur_index=None,
                cache_len: int = 0,
                rep_slice: Optional[Tuple[int, int]] = None,
                rep_gate: Optional[Tuple[Any, Any]] = None,
                lora_scale=None, block_tables=None, adapter_idx=None,
                pattern=None):
    """Run (a slice of) the layer stack.  Returns (x, new_caches,
    aux_loss_sum); in mode "train", which keeps no cache, the second value
    is (layers, held experts) int32: the rows each dropless MoE layer of
    the slice routed to each expert it holds, in layer order, or None
    where the slice has no such layer.

    ``pattern=cfg.prefix`` runs the leading layers (``init_stack`` made
    with the same pattern) in place of the repeating ones.

    ``rep_slice=(a, b)`` runs pattern repeats [a, b) — the SFL split point
    in repeat units.  ``caches``/returned caches follow the same slice.

    ``rep_gate=(lo, hi)`` — per-call boundary mask for heterogeneous split
    points (train mode only): repeat i of the scanned slice is applied iff
    ``lo <= i < hi`` (either bound may be None, a traced scalar, or a
    per-sample (B,) int32 array); gated repeats pass activations through
    unchanged, so the forward equals the [lo, hi) sub-stack and the
    backward masks their gradient contributions exactly.  The blocks still
    execute (uniform shapes keep the whole fleet one compiled scan) — the
    gate trades dead FLOPs for zero retraces.  With a per-sample gate the
    scalar MoE aux loss cannot be split per sample and is accumulated
    ungated.

    ``lora_scale`` overrides the default ``cfg.lora_alpha/cfg.lora_rank``
    adapter scaling — per-client ranks r_k scale by alpha/r_k (a traced
    scalar under the client vmap).

    ``adapter_idx`` (decode modes): per-slot adapter indices selecting out
    of POOLED lora leaves ``(R, A, ...)`` — the pool axis rides at
    position 1 so the depth scan still slices the leading repeat axis and
    each scanned block sees an ``(A, ...)`` pool (multi-tenant serving).
    """
    pattern = cfg.pattern if pattern is None else pattern
    P = len(pattern)
    leaves = jax.tree.leaves(stack_params)
    if mode == "train" and rep_slice is None and leaves \
            and leaves[0].shape[0] == 0:
        # no repeats here (the client of a split at the leading layers'
        # end): nothing to run
        return x, None, jnp.zeros((), jnp.float32)
    lora_stack = lora if lora is not None else tuple([None] * P)
    scale = (cfg.lora_alpha / cfg.lora_rank) if lora_scale is None else lora_scale
    gate_lo, gate_hi = rep_gate if rep_gate is not None else (None, None)
    gated = gate_lo is not None or gate_hi is not None
    if gated and mode != "train":
        raise NotImplementedError("rep_gate requires mode='train' "
                                  "(gated cache slots would be stale)")

    def _constrain(x):
        if not rt.dp_axes:
            return x
        from jax.sharding import PartitionSpec
        if rt.seq_shard and rt.tp_axis and mode in ("train", "prefill") \
                and x.shape[1] % 128 == 0:
            # sequence parallelism: between blocks the activations live
            # sharded over (dp, tp) — GSPMD turns the Megatron TP
            # all-reduce into reduce-scatter + all-gather (half traffic),
            # and norms/elementwise run on seq shards.
            spec = PartitionSpec(rt.dp_axes, rt.tp_axis,
                                 *([None] * (x.ndim - 2)))
        else:
            spec = PartitionSpec(rt.dp_axes, *([None] * (x.ndim - 1)))
        return jax.lax.with_sharding_constraint(x, spec)

    def body(carry, xs):
        x, aux = carry
        p_slices, l_slices, c_slices = xs
        c_outs = []
        for pi, pat in enumerate(pattern):
            x, c_out, a = apply_block(
                cfg, pat, p_slices[pi], x, positions=positions,
                lora=None if l_slices is None else l_slices[pi],
                lora_scale=scale, rt=rt, mode=mode,
                cache=None if c_slices is None else c_slices[pi],
                cur_index=cur_index, cache_len=cache_len,
                block_tables=block_tables, adapter_idx=adapter_idx)
            c_outs.append(c_out)
            aux = aux + a
        x = _constrain(x)       # keep scan-carried activations batch-sharded
        return (x, aux), tuple(c_outs)

    if rt.remat and mode == "train":
        if rt.remat_policy == "dots":
            body = jax.checkpoint(
                body,
                policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
        else:
            body = jax.checkpoint(body)

    params = stack_params
    lora_xs = lora_stack
    cache_xs = caches
    if rep_slice is not None:
        a, b = rep_slice
        sl = lambda t: None if t is None else jax.tree.map(lambda v: v[a:b], t)
        params = sl(params)
        lora_xs = sl(lora_xs)
        cache_xs = sl(cache_xs)

    if lora_xs is not None:
        # hoist adapter dtype casts out of the depth scan: one convert of
        # the stacked factors here instead of R per-step converts in the
        # compiled round body (per-layer convert absence is asserted in
        # tests/test_fused_dense.py)
        lora_xs = jax.tree.map(lambda v: _cast_like(x, v), lora_xs)

    # scan requires every xs leaf to share the leading (repeat) dim
    has_lora = lora_xs is not None and len(jax.tree.leaves(lora_xs)) > 0
    if not has_lora:
        # thread "no lora" through scan as a static None per step
        def body_nl(carry, xs2):
            p_s, c_s = xs2
            return body(carry, (p_s, None, c_s))
        run, xs = body_nl, (params, cache_xs)
    else:
        run, xs = body, (params, lora_xs, cache_xs)
    if gated:
        # heterogeneous split: select the repeat's output or the untouched
        # carry per boundary mask; scan xs gains the repeat index
        n_reps = jax.tree.leaves(params)[0].shape[0]
        inner = run

        def run_gated(carry, xs2):
            idx, rest = xs2
            x0, aux0 = carry
            (x1, aux1), couts = inner(carry, rest)
            keep = jnp.ones((), bool)
            if gate_lo is not None:
                keep = keep & (idx >= gate_lo)
            if gate_hi is not None:
                keep = keep & (idx < gate_hi)
            if keep.ndim:                      # per-sample boundary (B,)
                x2 = jnp.where(keep[:, None, None], x1, x0)
                aux2 = aux1
            else:
                x2 = jnp.where(keep, x1, x0)
                aux2 = jnp.where(keep, aux1, aux0)
            return (x2, aux2), couts

        run, xs = run_gated, (jnp.arange(n_reps, dtype=jnp.int32), xs)
    (x, aux), cache_out = jax.lax.scan(
        run, (x, jnp.zeros((), jnp.float32)), xs)
    if mode == "train":
        # (repeats, E) per position with a dropless MoE layer -> layers
        rows = [r for r in cache_out if r is not None]
        cache_out = (jnp.stack(rows, 1).reshape(-1, rows[0].shape[-1])
                     if rows else None)
    return x, cache_out, aux
