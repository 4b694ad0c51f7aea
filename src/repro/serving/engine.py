"""Continuous-batching serving engine (vLLM-style) with a fully in-graph
fused decode step.

Fixed-slot design: ``max_slots`` concurrent sequences share one KV cache
of length ``max_len``; every cache leaf carries the slot axis at position
1 (``(R, slots, ...)``), including the per-slot position rows — so slot
writes, the batched decode, and admission are all plain indexed updates on
one uniform pytree.

The fused path (default) removes every per-token host round-trip the
naive loop pays:

* ``step()`` is ONE jitted, buffer-donated call: flash-decode all slots
  at their own positions (``models.decode_step`` with a (B,) position
  vector), sample IN-GRAPH, advance per-slot counters, and return
  ``(next_tokens, done_mask, caches)`` — only two (slots,)-sized arrays
  cross back to the host per token;
* sampling keys are ``fold_in(fold_in(key, uid), token_index)`` per slot:
  each request owns its RNG stream, so outputs are independent of arrival
  order and slot occupancy (dead slots draw from their own dead stream,
  never consuming a live request's randomness);
* admission prefills into a power-of-two length bucket (compile count is
  bounded by log2(max_len) for ANY prompt-length mix) and writes the
  bucket's KV into the slot with per-slot ``dynamic_update_slice`` inside
  jit — not the full-cache host copy the naive path does;
* a slot whose cache fills (position reaching ``max_len``) is finished
  and freed instead of silently wrapping the ring.

``fused=False`` keeps the pre-PR execution shape (per-slot vmapped
decode, host-side sampling, full-cache admission copy) as the measured
baseline for the serving benchmark and the fused-vs-naive equivalence
test; it shares the per-request RNG streams so both modes sample
identically.

PAGED KV (default where eligible): instead of per-slot ``max_len`` slabs
the KV lives in a global page pool ``(KH, num_pages, page_size, D)`` with
per-slot ``(max_pages,)`` block tables — HBM is sized for the EXPECTED
total tokens in flight, not worst-case ``slots * max_len``, so the same
budget holds more concurrent sequences (``benchmarks/bench_traffic.py``
measures the TTFT/throughput win under Poisson traffic):

* pages allocate and free IN-GRAPH (``serving.paging``: a free-list
  stack carried in the donated step state) — a slot crossing a page
  boundary pops a page, finished slots push all theirs back, inside the
  same single jitted step; the one-call property of the fused path is
  preserved and asserted (``_jit_step_paged._cache_size() == 1``);
* admission is reservation-based: the host mirrors a conservative free
  count and admits a request only when its worst-case page demand
  (``ceil(min(P + max_new, max_len) / page_size)``) fits, so the
  in-graph allocator can never underflow (head-of-line FIFO
  backpressure otherwise — no silent drops);
* prefill is CHUNKED: prompts stream through ONE compiled chunk
  executable ``page_size`` tokens at a time (chunk == page), collapsing
  the log2(max_len) bucketed prefill variants to a single program;
* sampling keys are unchanged (``fold_in(fold_in(key, uid), idx)``), so
  outputs stay independent of page layout, slot index, and arrival
  order — the paged engine is token-identical to the slab engine.

``paged=False`` forces the PR-3 slab layout (the benchmark baseline);
mamba/windowed/frontend archs fall back to it automatically.

FAILURE HANDLING (paged engine only — the slab/naive paths stay frozen
baselines): the fused step additionally takes per-slot eviction flags, a
per-slot residency deadline, and a NaN-injection mask, all traced data —

* preemptive KV eviction: under page pressure (``preempt=True``) the host
  flags a strictly-lower-priority victim; a victim (or a slot whose
  ``deadline_steps`` residency budget fires) frees its pages INSIDE the
  fused donated step, is excluded from sampling, and requeues for
  chunked-prefill recompute of its prefix — delivered tokens are kept
  verbatim and the next token resumes the request's own
  ``fold_in(uid, token_idx)`` RNG stream, so (with greedy sampling) the
  completed output is identical to an un-preempted run;
* NaN/inf sentinel: non-finite logits (model blow-up or an injected
  poke) quarantine the slot — pages freed, ``Request.error`` set —
  instead of sampling garbage;
* malformed requests (empty, or no room to decode) are rejected at
  ``submit()`` with a typed ``AdmissionError`` rather than silently
  finishing empty;
* ``check_consistency()`` audits the host reservation mirror against the
  in-graph free list whenever the engine drains, resyncing (with a
  warning) if an external actor corrupted the counters.

All fault masks default to all-false, which the step consumes as
bit-exact no-ops: a fault-free run reproduces the pre-fault engine token
for token, and the one-call property still holds
(``_jit_step_paged._cache_size() == 1``).

MULTI-TENANT ADAPTERS (paged engine only): pass ``adapters=`` (an
``AdapterRegistry``) instead of ``lora=`` and every request carries a
``tenant`` id — one engine, one base model, one KV pool serve any number
of tenant adapters:

* the engine's lora argument becomes the registry's device POOL (leaves
  ``(R, A, ...)``) plus a per-slot adapter index ``_aslot``; the fused
  step gathers each slot's A/B tiles per row (the batched-gather LoRA
  kernel — ``kernels.lora_matmul.lora_matmul_gathered``), so a
  mixed-tenant batch decodes in the SAME single donated call
  (``_jit_step_paged._cache_size() == 1`` still holds);
* admission pins the tenants of live slots and ``acquire``s the new
  request's adapter — LRU-paging a cold tenant in from host memory —
  then threads the slot index through the one compiled chunk executable
  (the chunk slices the pool with a traced index: still one program);
* sampling keys gain the tenant fold —
  ``fold_in(fold_in(fold_in(key, tenant), uid), token_idx)`` — so a
  tenant's outputs are independent of co-residency, arrival order, and
  adapter slot assignment;
* ``tenant_quota`` caps live slots per tenant (0 = unlimited): the
  scheduler admits the first FIFO entry whose tenant is under quota, so
  one chatty tenant cannot monopolize the batch;
* ``stats["tenant_tokens"]`` counts delivered tokens per tenant and
  ``stats["adapter_swaps"]`` mirrors the registry's pool loads;
* a registry with ``pool_size == 1`` and constant index is bit-identical
  to the single-adapter engine (the pool constant-folds in
  ``layers.dense``), and a non-multi-tenant engine's traced step is
  unchanged by construction (the adapter operands are absent, not
  zeros).
"""
from __future__ import annotations

import collections
from dataclasses import dataclass, field
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..models import model as model_mod
from ..models.generate import (SampleConfig, sample_logits,
                               sample_logits_per_key)
from ..models.stack import Runtime, default_serve_runtime
from . import paging


class AdmissionError(ValueError):
    """A request the engine can NEVER serve, rejected at ``submit()`` with
    a typed reason (``empty-prompt`` | ``prompt-too-long``) — instead of
    the silent done-with-no-output a malformed request used to get."""

    def __init__(self, reason: str, msg: str):
        super().__init__(msg)
        self.reason = reason


@dataclass
class Request:
    uid: int
    prompt: List[int]
    max_new_tokens: int = 32
    eos_id: int = -1
    priority: int = 0              # preemption: lower loses its slot first
    deadline_steps: Optional[int] = None   # max decode steps per residency
    tenant: int = 0                # adapter owner (multi-tenant serving)
    # filled by the engine
    output: List[int] = field(default_factory=list)
    done: bool = False
    preempted: int = 0             # times evicted + requeued
    error: Optional[str] = None    # quarantine reason (non-finite logits)


def _is_pos(kp) -> bool:
    last = kp[-1]
    return str(getattr(last, "key", getattr(last, "idx", last))) == "pos"


def bucket_len(n: int, max_len: int) -> int:
    """Smallest power of two >= n (floor 8), capped at the largest power
    of two <= max_len: mixed prompt lengths compile at most log2(max_len)
    prefill variants.  For a non-power-of-two ``max_len`` the cap rounds
    DOWN — capping at ``max_len`` itself would leak a non-power-of-two
    shape into the compile cache and (worse) return a bucket shorter than
    the prompt.  Prompts longer than the cap are the caller's problem
    (the engine prefills them at exact length); the assert keeps that
    contract honest."""
    b = 8
    while b < n:
        b *= 2
    cap = 1 << (max_len.bit_length() - 1)
    b = min(b, cap)
    assert b >= n, (
        f"prompt length {n} exceeds the largest bucket {b} for "
        f"max_len={max_len}; use exact-length prefill for gap prompts")
    return b


class ServingEngine:
    def __init__(self, cfg, params, *, lora=None, rt: Optional[Runtime] = None,
                 max_slots: int = 4, max_len: int = 256,
                 sc: SampleConfig = SampleConfig(greedy=True), seed: int = 0,
                 fused: bool = True, prefill_buckets: bool = True,
                 paged: Optional[bool] = None, page_size: int = 16,
                 num_pages: Optional[int] = None, preempt: bool = False,
                 adapters=None, tenant_quota: int = 0):
        if getattr(cfg, "frontend", None):
            raise NotImplementedError(
                "ServingEngine serves text-only requests; frontend archs "
                "need a frontend_emb-aware admission path")
        self.cfg, self.params, self.lora = cfg, params, lora
        self.rt = rt if rt is not None else default_serve_runtime()
        self.max_slots, self.max_len, self.sc = max_slots, max_len, sc
        self.fused = fused
        # right-padded bucket prefill assumes pad entries can be masked out
        # of an attention cache tail; recurrent (mamba) state and windowed
        # rings have no such tail — those archs prefill at exact length
        self.prefill_buckets = (prefill_buckets and not cfg.attn_window and
                                all(p.mixer == "attention" for p in cfg.pattern))
        # paged KV needs the same length-contiguous attention-only shape,
        # and the chunk == page layout needs max_len to divide into pages
        paged_ok = (fused and not cfg.attn_window and
                    all(p.mixer == "attention" for p in cfg.pattern))
        if paged is None:
            paged = paged_ok and max_len % page_size == 0
        elif paged and not fused:
            raise ValueError("paged KV requires the fused engine "
                             "(page alloc/free live inside the fused step)")
        elif paged and not paged_ok:
            raise NotImplementedError(
                "paged KV requires an attention-only, non-windowed pattern")
        self.paged = paged
        # multi-tenant adapter serving: the registry's device pool replaces
        # the single lora argument; requires the paged engine (the chunk
        # prefill and the fused gather step carry the adapter operands)
        self.adapters = adapters
        self.tenant_quota = tenant_quota
        if adapters is not None:
            if lora is not None:
                raise ValueError("pass either lora= or adapters=, not both")
            if not self.paged:
                raise NotImplementedError(
                    "multi-tenant adapters require the paged engine "
                    "(fused, attention-only, max_len % page_size == 0)")
            if adapters.pool_size < max_slots:
                # with pool >= slots an admission can always pin the <=
                # max_slots-1 live tenants and still find a victim slot
                raise ValueError(
                    f"adapter pool_size={adapters.pool_size} must be >= "
                    f"max_slots={max_slots}")
        elif tenant_quota:
            raise ValueError("tenant_quota needs adapters=")
        self.key = jax.random.key(seed)

        self.queue: collections.deque[Request] = collections.deque()
        self.slots: List[Optional[Request]] = [None] * max_slots

        # per-slot device state (fused path reads/writes these in-graph)
        B = max_slots
        self._last = jnp.zeros((B,), jnp.int32)
        self._positions = jnp.zeros((B,), jnp.int32)   # next write index
        self._live = jnp.zeros((B,), bool)
        self._uids = jnp.full((B,), -1, jnp.int32)
        self._ngen = jnp.zeros((B,), jnp.int32)
        self._maxnew = jnp.zeros((B,), jnp.int32)
        self._eos = jnp.full((B,), -1, jnp.int32)
        # host-side mirrors for the legacy (fused=False) loop
        self._np_positions = np.zeros(B, np.int64)
        self._np_last = np.zeros(B, np.int64)
        # failure handling (paged only): per-slot decode-step age vs the
        # request's residency deadline, host-set eviction / NaN-injection
        # flags (cleared every step), and recovery counters
        self.preempt = preempt
        self._age = jnp.zeros((B,), jnp.int32)
        self._deadline = jnp.full((B,), -1, jnp.int32)
        self._evict_req = np.zeros(B, bool)     # crash / page-pressure evict
        self._evict_behind = np.zeros(B, bool)  # requeue behind queue head
        self._nan_poke = np.zeros(B, bool)      # faults.inject: NaN logits
        self.stats = {"preemptions": 0, "deadline_preemptions": 0,
                      "quarantined": 0, "recomputed_tokens": 0,
                      "resyncs": 0, "tenant_tokens": {}, "adapter_swaps": 0}
        # multi-tenant per-slot state: adapter pool slot + tenant id
        # (inert placeholders when adapters is None — never passed to jit)
        self._aslot = jnp.zeros((B,), jnp.int32)
        self._tenant = jnp.zeros((B,), jnp.int32)

        if self.paged:
            if max_len % page_size:
                raise ValueError(f"max_len={max_len} must be a multiple of "
                                 f"page_size={page_size} (chunk == page)")
            self.page_size = page_size
            self.max_pages = max_len // page_size
            # default pool matches slab capacity exactly (+ the null page):
            # callers shrink num_pages to oversubscribe slots against HBM
            self.num_pages = (num_pages if num_pages is not None
                              else max_slots * self.max_pages + 1)
            if self.num_pages < self.max_pages + 1:
                raise ValueError("num_pages too small for a single request")
            self.caches = model_mod.init_paged_cache(
                cfg, self.num_pages, page_size, jnp.float32)
            self._bt = jnp.zeros((B, self.max_pages), jnp.int32)
            self._pager = paging.init_pager(self.num_pages)
            # conservative host mirror of the in-graph free count: admission
            # reserves worst-case pages per request, so in-graph demand
            # (lazy, actual) can never underflow the stack
            self._free_host = self.num_pages - 1
            self._reserved = [0] * B
        else:
            self.caches = model_mod.init_cache(cfg, max_slots, max_len,
                                               jnp.float32)

        self._build_jits()

    # ------------------------------------------------------------------
    # compiled calls
    # ------------------------------------------------------------------
    def _build_jits(self) -> None:
        cfg, rt, sc = self.cfg, self.rt, self.sc
        max_len, B = self.max_len, self.max_slots
        base_key = self.key

        def _slot_keys(uids, ngen, tenants=None):
            # multi-tenant: fold the tenant id in FIRST, so a tenant's
            # stream is independent of co-residency, arrival order and
            # adapter slot; single-tenant keys are byte-identical to the
            # pre-adapter engine (no fold at all, not a fold of zero)
            def one(u, n, t=None):
                k = base_key if t is None else jax.random.fold_in(base_key, t)
                return jax.random.fold_in(jax.random.fold_in(k, u), n)
            if tenants is None:
                return jax.vmap(one)(uids, ngen)
            return jax.vmap(one)(uids, ngen, tenants)

        # -- fused decode step: decode + sample + bookkeeping, one call --
        def _step(params, lora, caches, last, positions, live, uids, ngen,
                  maxnew, eos):
            logits, caches = model_mod.decode_step(
                cfg, params, last[:, None], caches, positions, lora=lora, rt=rt)
            nxt = sample_logits_per_key(logits, _slot_keys(uids, ngen), sc)
            nxt = jnp.where(live, nxt, 0)
            ngen1 = ngen + live.astype(jnp.int32)
            done = live & ((nxt == eos) | (ngen1 >= maxnew) |
                           (positions + 1 >= max_len))
            return (nxt, done, caches, jnp.where(live, nxt, last),
                    positions + live.astype(jnp.int32), live & ~done, ngen1)

        self._jit_step = jax.jit(_step, donate_argnums=(2, 3, 4, 5, 7))

        if self.paged:
            PS, MP = self.page_size, self.max_pages

            # -- fused PAGED decode step: preempt + page alloc + decode +
            #    NaN sentinel + sample + bookkeeping + page free, ONE
            #    donated call --------------------------------------------
            # ``aslot``/``tenants`` are the multi-tenant operands: absent
            # (None) for a single-adapter engine — the traced program is
            # then literally the pre-adapter one — and (B,) int32 vectors
            # when serving an AdapterRegistry pool, in which case ``lora``
            # is the pool and the decode gathers each row's adapter
            def _step_paged(params, lora, caches, pager, bt, last, positions,
                            live, uids, ngen, maxnew, eos, age, deadline,
                            evict, nan_poke, aslot=None, tenants=None):
                bidx = jnp.arange(B)
                # preemption first: a slot the host marked for eviction or
                # whose residency deadline fired gives its pages back to
                # the pool THIS step (free_pages zeroes its block-table
                # row; the victim still flows through the batched decode
                # reading the null page, but is excluded from sampling and
                # every state write).  All-false masks are bit-exact
                # no-ops, so a fault-free step reproduces the pre-fault
                # engine token for token.
                victim = live & (evict | ((deadline >= 0) & (age >= deadline)))
                pager, bt = paging.free_pages(pager, bt, victim)
                ok = live & ~victim
                # a live slot about to write at a page boundary needs a
                # fresh page (prefill only covered [0, ceil(P/PS)*PS));
                # each boundary is crossed exactly once, so this is the
                # request's lazy, actual page demand
                need = ok & (positions % PS == 0)
                pager, newp, _ = paging.alloc_pages(pager, need)
                page_idx = jnp.minimum(positions // PS, MP - 1)
                cur = bt[bidx, page_idx]
                bt = bt.at[bidx, page_idx].set(jnp.where(need, newp, cur))
                logits, caches = model_mod.paged_decode_step(
                    cfg, params, last[:, None], caches, bt, positions,
                    lora=lora, rt=rt, adapter_idx=aslot)
                # NaN/inf sentinel: a slot whose logits go non-finite
                # (model blow-up, or an injected poke) is quarantined —
                # its pages free below and the host records the error —
                # instead of sampling garbage into the output stream
                logits = jnp.where(nan_poke[:, None], jnp.nan, logits)
                finite = jnp.all(jnp.isfinite(logits), axis=-1)
                bad = ok & ~finite
                ok = ok & finite
                safe = jnp.where(finite[:, None], logits, 0.0)
                nxt = sample_logits_per_key(
                    safe, _slot_keys(uids, ngen, tenants), sc)
                nxt = jnp.where(ok, nxt, 0)
                ngen1 = ngen + ok.astype(jnp.int32)
                done = ok & ((nxt == eos) | (ngen1 >= maxnew) |
                             (positions + 1 >= max_len))
                pager, bt = paging.free_pages(pager, bt, done | bad)
                live1 = ok & ~done
                return (nxt, done, victim, bad, caches, pager, bt,
                        jnp.where(ok, nxt, last),
                        positions + ok.astype(jnp.int32), live1,
                        ngen1, jnp.where(live1, age + 1, 0))

            self._jit_step_paged = jax.jit(
                _step_paged, donate_argnums=(2, 3, 4, 5, 6, 7, 9, 12))

            # -- chunked prefill: ONE compiled executable serves every
            #    chunk of every prompt (start/true_len/uid/slot traced);
            #    ``tok_idx`` is the request's next token index — 0 for a
            #    fresh prompt, len(output) for a preempted request being
            #    recomputed, so the requeued request resumes its OWN RNG
            #    stream and continues token-identically -------------------
            def _chunk(params, lora, caches, pager, bt, tokens, slot, start,
                       true_len, uid, tok_idx, aslot=None, tenant=None):
                pager, newp, _ = paging.alloc_pages(
                    pager, jnp.ones((1,), bool))
                bt = bt.at[slot, start // PS].set(newp[0])
                row = jax.lax.dynamic_index_in_dim(bt, slot, 0,
                                                   keepdims=False)
                li = jnp.clip(true_len - 1 - start, 0, PS - 1)
                if aslot is not None:
                    # multi-tenant: ``lora`` is the registry pool; slice
                    # this request's adapter out with a TRACED index so the
                    # one-chunk-executable property survives any tenant mix
                    lora = jax.tree.map(
                        lambda v: jax.lax.dynamic_index_in_dim(
                            v, aslot, 1, keepdims=False), lora)
                logits, caches = model_mod.paged_prefill_chunk(
                    cfg, params, tokens, caches, row, start, li,
                    lora=lora, rt=rt)
                k = (base_key if tenant is None
                     else jax.random.fold_in(base_key, tenant))
                k = jax.random.fold_in(jax.random.fold_in(k, uid), tok_idx)
                tok0 = sample_logits(logits, k, sc)[0]
                return tok0, caches, pager, bt

            self._jit_chunk = jax.jit(_chunk, donate_argnums=(2, 3, 4))

            # -- record a claimed slot's adapter slot + tenant id --------
            def _claim_mt(aslot_arr, tenant_arr, slot, a, t):
                return aslot_arr.at[slot].set(a), tenant_arr.at[slot].set(t)

            self._jit_claim_mt = jax.jit(_claim_mt, donate_argnums=(0, 1))

            # -- claim a slot after its prompt streamed through ----------
            def _claim(last, positions, live, uids, ngen, maxnew, eos, age,
                       deadline, slot, tok0, true_len, uid, ngen0,
                       req_maxnew, req_eos, req_deadline):
                return (last.at[slot].set(tok0),
                        positions.at[slot].set(true_len),
                        live.at[slot].set(True), uids.at[slot].set(uid),
                        ngen.at[slot].set(ngen0),
                        maxnew.at[slot].set(req_maxnew),
                        eos.at[slot].set(req_eos), age.at[slot].set(0),
                        deadline.at[slot].set(req_deadline))

            self._jit_claim = jax.jit(
                _claim, donate_argnums=(0, 1, 2, 3, 4, 5, 6, 7, 8))

            # -- release a slot's pages (request finished mid-prefill) ---
            def _release(pager, bt, slot):
                return paging.free_pages(pager, bt,
                                         jnp.arange(B) == slot)

            self._jit_release = jax.jit(_release, donate_argnums=(0, 1))

        # -- bucketed prefill: KV for one request + its first token ------
        def _prefill(params, lora, tokens, true_len, uid):
            logits, cache1 = model_mod.prefill(
                cfg, params, tokens, lora=lora, rt=rt,
                cache_len=tokens.shape[1], logit_index=true_len - 1)
            k = jax.random.fold_in(jax.random.fold_in(base_key, uid), 0)
            tok0 = sample_logits(logits, k, sc)[0]
            return tok0, cache1

        self._jit_prefill = jax.jit(_prefill)

        # -- legacy full-cache prefill (naive admission path) ------------
        def _prefill_full(params, lora, tokens, uid):
            logits, cache1 = model_mod.prefill(cfg, params, tokens, lora=lora,
                                               rt=rt, cache_len=max_len)
            k = jax.random.fold_in(jax.random.fold_in(base_key, uid), 0)
            return sample_logits(logits, k, sc)[0], cache1

        self._jit_prefill_full = jax.jit(_prefill_full)

        # -- in-graph slot admission: per-slot dynamic_update_slice ------
        def _admit_write(caches, last, positions, live, uids, ngen, maxnew,
                         eos, cache1, slot, tok0, true_len, uid, req_maxnew,
                         req_eos):
            def write(kp, big, one):
                if _is_pos(kp):
                    # one: (R, 1, Lb) — mark the padding tail (positions
                    # >= true_len) empty, extend to the slot's full row
                    row = jnp.where(one[:, 0] < true_len, one[:, 0], -1)
                    row = jnp.pad(row, ((0, 0), (0, big.shape[2] - row.shape[1])),
                                  constant_values=-1)
                    return jax.lax.dynamic_update_slice(
                        big, row[:, None], (0, slot, 0))
                return jax.lax.dynamic_update_slice(
                    big, one, (0, slot) + (0,) * (one.ndim - 2))

            caches = jax.tree_util.tree_map_with_path(write, caches, cache1)
            return (caches, last.at[slot].set(tok0),
                    positions.at[slot].set(true_len), live.at[slot].set(True),
                    uids.at[slot].set(uid), ngen.at[slot].set(1),
                    maxnew.at[slot].set(req_maxnew), eos.at[slot].set(req_eos))

        self._jit_admit = jax.jit(_admit_write,
                                  donate_argnums=(0, 1, 2, 3, 4, 5, 6, 7))

        # -- legacy decode: per-slot vmap, logits back to host -----------
        def _decode(params, lora, toks, caches, positions):
            def one(tok, cache_slot, pos):
                cache_b = jax.tree.map(lambda v: v[:, None], cache_slot)
                logits, new_cache = model_mod.decode_step(
                    cfg, params, tok[None, None], cache_b, pos,
                    lora=lora, rt=rt)
                return logits[0], jax.tree.map(lambda v: v[:, 0], new_cache)

            return jax.vmap(one, in_axes=(0, 1, 0),
                            out_axes=(0, 1))(toks, caches, positions)

        self._jit_decode = jax.jit(_decode)

    # ------------------------------------------------------------------
    def submit(self, req: Request) -> None:
        if len(req.prompt) == 0:
            raise AdmissionError("empty-prompt",
                                 f"request {req.uid}: empty prompt")
        if len(req.prompt) >= self.max_len:
            raise AdmissionError(
                "prompt-too-long",
                f"request {req.uid}: prompt length {len(req.prompt)} leaves "
                f"no room to decode (max_len={self.max_len})")
        self.queue.append(req)

    def prefill_compiles(self) -> int:
        """Number of distinct prefill programs compiled so far (paged:
        exactly one chunk executable for ANY prompt-length mix; slab:
        bounded by the power-of-two bucket count)."""
        if self.paged:
            return self._jit_chunk._cache_size()
        fn = self._jit_prefill if self.fused else self._jit_prefill_full
        return fn._cache_size()

    def pages_in_use(self) -> int:
        """Pages currently allocated out of the in-graph pool."""
        return self.num_pages - 1 - int(self._pager["head"])

    def check_consistency(self, resync: bool = True) -> bool:
        """Audit the host reservation mirror against the in-graph free
        list: the mirror must account for every page (free + reserved =
        pool) and the allocator can never have handed out more pages than
        were reserved (lazy demand <= worst case).  On drift — which only
        an external actor poking ``_free_host``/``_reserved`` can cause —
        warn and rebuild the mirror from the live slots, so one corrupted
        counter degrades admission throughput for a moment instead of
        deadlocking the queue or underflowing the allocator forever.
        Returns True when the mirror was consistent."""
        used = self.pages_in_use()
        reserved = sum(self._reserved)
        ok = (self._free_host == self.num_pages - 1 - reserved
              and used <= reserved)
        if not ok and resync:
            import warnings
            warnings.warn(
                f"page-accounting drift: free_host={self._free_host} "
                f"reserved={reserved} in_use={used} "
                f"pool={self.num_pages - 1}; resyncing from live slots",
                RuntimeWarning, stacklevel=2)
            self._reserved = [self._worst_pages(r) if r is not None else 0
                              for r in self.slots]
            self._free_host = self.num_pages - 1 - sum(self._reserved)
            self.stats["resyncs"] += 1
        return ok

    def _worst_pages(self, req: Request) -> int:
        """Worst-case page demand of one request: every position it can
        ever write KV at is < min(P + max_new, max_len)."""
        toks = min(len(req.prompt) + req.max_new_tokens, self.max_len)
        return -(-toks // self.page_size)

    def _lora_arg(self):
        """What the compiled calls receive as ``lora``: the registry's
        (possibly just-reloaded) device pool under multi-tenant serving,
        else the single adapter."""
        return self.adapters.pool if self.adapters is not None else self.lora

    def _note_token(self, req: Request) -> None:
        """Per-tenant delivered-token accounting (multi-tenant only)."""
        if self.adapters is not None:
            tt = self.stats["tenant_tokens"]
            tt[req.tenant] = tt.get(req.tenant, 0) + 1

    # ------------------------------------------------------------------
    # admission
    # ------------------------------------------------------------------
    def _admit_one_paged(self, s: int, req: Request) -> bool:
        """Stream ``req``'s prefix through the compiled chunk executable
        (one page per chunk) and claim slot ``s``.  The caller has already
        reserved ``_worst_pages(req)`` in the host mirror.

        The prefix is prompt + already-delivered output: a fresh request
        prefills its prompt and samples token 0; a preempted request being
        recomputed prefills everything it had (its delivered tokens are
        NEVER re-sampled — they stay in ``output`` verbatim) and samples
        its next token index from its own RNG stream, continuing the
        sequence exactly where eviction cut it.  Returns False when the
        request finished on this first token (pages released, slot stays
        free)."""
        n = len(req.output)                 # tokens already delivered
        prefix = list(req.prompt) + list(req.output)
        P, PS = len(prefix), self.page_size
        if req.preempted:
            self.stats["recomputed_tokens"] += P
        mt = ()
        if self.adapters is not None:
            # pin the tenants a live decode batch is actively gathering
            # from; slot ``s`` is still free here, so at most max_slots-1
            # tenants are pinned and (pool_size >= max_slots) a victim
            # always exists for a cold tenant
            pinned = {r.tenant for r in self.slots if r is not None}
            aslot_i = self.adapters.acquire(req.tenant, pinned=pinned)
            self.stats["adapter_swaps"] = self.adapters.stats["swaps"]
            mt = (jnp.int32(aslot_i), jnp.int32(req.tenant))
        tok_d = None
        for start in range(0, P, PS):
            m = min(PS, P - start)
            chunk = prefix[start:start + m] + [0] * (PS - m)
            tokens = jnp.asarray(chunk, jnp.int32)[None]
            (tok_d, self.caches, self._pager, self._bt) = self._jit_chunk(
                self.params, self._lora_arg(), self.caches, self._pager,
                self._bt, tokens, jnp.int32(s), jnp.int32(start),
                jnp.int32(P), jnp.int32(req.uid), jnp.int32(n), *mt)
        tok = int(tok_d)
        req.output.append(tok)
        self._note_token(req)
        if (tok == req.eos_id) or (len(req.output) >= req.max_new_tokens) \
                or (P >= self.max_len):     # prefix filled the cache
            req.done = True
            self._pager, self._bt = self._jit_release(
                self._pager, self._bt, jnp.int32(s))
            self._free_host += self._reserved[s]
            self._reserved[s] = 0
            return False
        dl = -1 if req.deadline_steps is None else int(req.deadline_steps)
        (self._last, self._positions, self._live, self._uids, self._ngen,
         self._maxnew, self._eos, self._age, self._deadline) = self._jit_claim(
            self._last, self._positions, self._live, self._uids, self._ngen,
            self._maxnew, self._eos, self._age, self._deadline, jnp.int32(s),
            tok_d, jnp.int32(P), jnp.int32(req.uid), jnp.int32(n + 1),
            jnp.int32(req.max_new_tokens), jnp.int32(req.eos_id),
            jnp.int32(dl))
        if mt:
            self._aslot, self._tenant = self._jit_claim_mt(
                self._aslot, self._tenant, jnp.int32(s), *mt)
        self.slots[s] = req
        return True

    def _admit_one(self, s: int, req: Request) -> bool:
        """Prefill ``req`` and claim slot ``s``.  Returns False when the
        request finished on its very first token (slot stays free)."""
        P = len(req.prompt)
        if P >= self.max_len:       # no room to decode even one token
            req.done = True
            return False
        if self.paged:
            return self._admit_one_paged(s, req)
        if self.fused:
            # prompts longer than the largest power-of-two bucket (only
            # possible for non-power-of-two max_len) prefill at exact
            # length — bucket_len would otherwise return a bucket < P
            cap = 1 << (self.max_len.bit_length() - 1)
            use_bucket = self.prefill_buckets and P <= cap
            Lb = bucket_len(P, self.max_len) if use_bucket else P
            tokens = jnp.asarray(req.prompt + [0] * (Lb - P), jnp.int32)[None]
            tok0_d, cache1 = self._jit_prefill(self.params, self.lora, tokens,
                                               jnp.int32(P), jnp.int32(req.uid))
        else:
            tokens = jnp.asarray(req.prompt, jnp.int32)[None]
            tok0_d, cache1 = self._jit_prefill_full(self.params, self.lora,
                                                    tokens, jnp.int32(req.uid))
        tok0 = int(tok0_d)
        req.output.append(tok0)
        if (tok0 == req.eos_id) or (req.max_new_tokens <= 1):
            req.done = True
            return False
        if self.fused:
            (self.caches, self._last, self._positions, self._live, self._uids,
             self._ngen, self._maxnew, self._eos) = self._jit_admit(
                self.caches, self._last, self._positions, self._live,
                self._uids, self._ngen, self._maxnew, self._eos, cache1,
                jnp.int32(s), tok0_d, jnp.int32(P), jnp.int32(req.uid),
                jnp.int32(req.max_new_tokens), jnp.int32(req.eos_id))
        else:
            # pre-PR execution shape: copy the WHOLE cache tree per admit
            self.caches = jax.tree.map(
                lambda big, one: big.at[:, s].set(one[:, 0]),
                self.caches, cache1)
            self._np_positions[s] = P
            self._np_last[s] = tok0
        self.slots[s] = req
        return True

    def _request_preempt(self, head: Request) -> None:
        """Page pressure: pick a live victim of STRICTLY lower priority
        than the stalled queue head (strictness prevents same-priority
        livelock) and flag it for in-graph eviction on the next step.
        Ties: the victim holding the most pages, then the lowest slot."""
        cand = [s for s, r in enumerate(self.slots)
                if r is not None and r.priority < head.priority
                and not self._evict_req[s]]
        if not cand:
            return
        victim = min(cand, key=lambda s: (self.slots[s].priority,
                                          -self._reserved[s], s))
        self._evict_req[victim] = True
        # the victim must requeue BEHIND the head it yielded to, or the
        # two would evict each other forever
        self._evict_behind[victim] = True

    def _admissible_index(self) -> int:
        """Index of the first queued request whose tenant is under
        ``tenant_quota`` live slots (-1 if none): one chatty tenant's
        backlog cannot monopolize the batch, but FIFO order is preserved
        within what the quota allows."""
        if self.adapters is None or not self.tenant_quota:
            return 0 if self.queue else -1
        livec = collections.Counter(
            r.tenant for r in self.slots if r is not None)
        for i, req in enumerate(self.queue):
            if livec[req.tenant] < self.tenant_quota:
                return i
        return -1

    def _admit(self) -> None:
        for s in range(self.max_slots):
            while self.slots[s] is None and self.queue:
                qi = self._admissible_index()
                if qi < 0:
                    return          # every queued tenant is at quota
                if qi:
                    # promote the first under-quota request to the head so
                    # the FIFO backpressure below holds for IT, not for a
                    # quota-blocked entry in front of it
                    req = self.queue[qi]
                    del self.queue[qi]
                    self.queue.appendleft(req)
                if self.paged:
                    head = self.queue[0]
                    if len(head.prompt) < self.max_len:
                        worst = self._worst_pages(head)
                        if worst > self._free_host:
                            # FIFO backpressure: hold the whole queue until
                            # enough pages free (no reordering, no drops);
                            # with preempt=True, additionally evict a
                            # lower-priority slot so they free sooner
                            if self.preempt:
                                self._request_preempt(head)
                            return
                        self._free_host -= worst
                        self._reserved[s] = worst
                if self._admit_one(s, self.queue.popleft()):
                    break

    # ------------------------------------------------------------------
    # stepping
    # ------------------------------------------------------------------
    def step(self) -> int:
        """Admit + one decode round for all live slots.  Returns the number
        of live sequences decoded this step."""
        self._admit()
        live = [s for s in range(self.max_slots) if self.slots[s] is not None]
        if not live:
            return 0
        if self.paged:
            evict_np = self._evict_req.copy()
            behind_np = self._evict_behind.copy()
            # a copy: on the CPU a host array can be handed to the step
            # without one, and the reset below could land before it runs
            poke_np = self._nan_poke.copy()
            mt = ((self._aslot, self._tenant)
                  if self.adapters is not None else ())
            (nxt, done, victim, bad, self.caches, self._pager, self._bt,
             self._last, self._positions, self._live, self._ngen,
             self._age) = self._jit_step_paged(
                self.params, self._lora_arg(), self.caches, self._pager,
                self._bt, self._last, self._positions, self._live,
                self._uids, self._ngen, self._maxnew, self._eos, self._age,
                self._deadline, jnp.asarray(evict_np),
                jnp.asarray(poke_np), *mt)
            self._evict_req[:] = False
            self._evict_behind[:] = False
            self._nan_poke[:] = False
            nxt_h, done_h = np.asarray(nxt), np.asarray(done)
            victim_h, bad_h = np.asarray(victim), np.asarray(bad)
            front: List[Request] = []
            for s in live:
                req = self.slots[s]
                if victim_h[s]:
                    # preempted: pages freed in-graph this step; requeue
                    # for chunked-prefill recompute of its prefix (its
                    # delivered tokens are preserved, not re-sampled)
                    req.preempted += 1
                    self.slots[s] = None
                    self._free_host += self._reserved[s]
                    self._reserved[s] = 0
                    self.stats["preemptions"] += 1
                    if not evict_np[s]:
                        self.stats["deadline_preemptions"] += 1
                    if behind_np[s] and self.queue:
                        self.queue.insert(1, req)   # behind the head it
                    else:                           # yielded its pages to
                        front.append(req)
                    continue
                if bad_h[s]:
                    # quarantined: non-finite logits — fail the request
                    # with a typed error instead of emitting garbage
                    req.error = "non-finite logits"
                    req.done = True
                    self.slots[s] = None
                    self._free_host += self._reserved[s]
                    self._reserved[s] = 0
                    self.stats["quarantined"] += 1
                    continue
                req.output.append(int(nxt_h[s]))
                self._note_token(req)
                if done_h[s]:
                    req.done = True
                    self.slots[s] = None
                    # pages were pushed back in-graph this same step;
                    # return the full reservation to the host mirror
                    self._free_host += self._reserved[s]
                    self._reserved[s] = 0
            for req in reversed(front):     # oldest work back to the front
                self.queue.appendleft(req)
        elif self.fused:
            (nxt, done, self.caches, self._last, self._positions, self._live,
             self._ngen) = self._jit_step(
                self.params, self.lora, self.caches, self._last,
                self._positions, self._live, self._uids, self._ngen,
                self._maxnew, self._eos)
            nxt_h, done_h = np.asarray(nxt), np.asarray(done)
            for s in live:
                req = self.slots[s]
                req.output.append(int(nxt_h[s]))
                if done_h[s]:
                    req.done = True
                    self.slots[s] = None
        else:
            toks = jnp.asarray(self._np_last, jnp.int32)
            pos = jnp.asarray(self._np_positions, jnp.int32)
            logits, self.caches = self._jit_decode(self.params, self.lora,
                                                   toks, self.caches, pos)
            uids = jnp.asarray([r.uid if r is not None else -1
                                for r in self.slots], jnp.int32)
            ngen = jnp.asarray([len(r.output) if r is not None else 0
                                for r in self.slots], jnp.int32)
            keys = jax.vmap(lambda u, n: jax.random.fold_in(
                jax.random.fold_in(self.key, u), n))(uids, ngen)
            nxt = np.asarray(sample_logits_per_key(logits, keys, self.sc))
            for s in live:
                req = self.slots[s]
                tok = int(nxt[s])
                req.output.append(tok)
                self._np_positions[s] += 1
                self._np_last[s] = tok
                if (tok == req.eos_id) or \
                        (len(req.output) >= req.max_new_tokens) or \
                        (self._np_positions[s] >= self.max_len):
                    req.done = True
                    self.slots[s] = None
        return len(live)

    def run(self, max_steps: int = 10_000) -> None:
        for _ in range(max_steps):
            if not self.queue and all(s is None for s in self.slots):
                # drained: audit the reservation mirror (all pages home)
                if self.paged:
                    self.check_consistency()
                return
            self.step()
