"""SflLLM runtime — Algorithm 1 of the paper.

Faithful split-federated semantics:

* K clients each hold the embedding + the first ``ell_c`` layers (frozen)
  plus their *own* client-side LoRA adapter DeltaW_{c,k};
* the main server holds the remaining layers + LM head (frozen) plus one
  shared server-side adapter DeltaW_s;
* a local step is: client FP -> upload (s_k, y_k) -> server FP + loss over
  the pooled batch (eq. 2) -> server BP + adapter update (eq. 5) ->
  download dL/ds_k -> client BP + adapter update (eq. 6);
* every I local steps the federated server aggregates the client adapters
  (eq. 7, ``core.aggregation.fedavg``) and broadcasts the result.

The round engine compiles one whole global round — ``lax.scan`` over the I
local steps followed by in-graph FedAvg — into a single jitted call
(``train_round``), so the host dispatches once per round instead of K*I
times.  State buffers are donated between rounds, and when a mesh with a
``("clients",)`` axis is supplied the vmapped client FP/BP runs
data-parallel across devices (see ``sharding.specs.sfl_state_shardings``).

The information flow is exactly the paper's: the server function only ever
receives split-layer activations + labels (never raw tokens), and clients
only ever receive activation gradients.  Client compute is batched with
``jax.vmap`` over the client axis — the parallel-clients property SFL adds
over SL.

Heterogeneous fleets (the Section VI joint optimization as the *operating
mode*, not just a delay model): pass per-client split points ``ell_c``
(sequence) and LoRA ranks ``ranks``, or build the trainer straight from a
resource-allocation decision with :meth:`SflLLM.from_allocation`.  Client
adapters are stored zero-padded to r_max with per-client slot masks
(``core.lora.client_slot_masks``) keeping dead rows/cols exactly zero
through masked optimizer updates; FedAvg becomes slot-wise rank-aware
(``core.aggregation.fedavg_het``); each client scans to max(ell_k) with a
boundary gate selecting its own split activation, and the server re-enters
each client's stream at its own depth via a per-sample gate
(``models.stack.apply_stack(rep_gate=...)``).  The whole mixed fleet still
compiles to ONE jitted round (uniform shapes; masks make the padded math
exact) — when every client is configured identically, the legacy
homogeneous code path is taken unchanged, bit for bit.

Dynamic wireless rounds (the time axis): real fleets fade, straggle and
drop out *between* rounds.  ``train_round`` accepts a :class:`RoundDynamics`
of per-round **traced** inputs — channel state (uplink rates, compute), a
round deadline, an explicit participation mask, and optionally a whole
re-allocated (ell_k, r_k) decision as arrays (``allocation_dynamics``) —
so every round of a time-varying episode reuses ONE compiled trace.
Straggler dropout is evaluated in-graph (the traced twin of the Section V
delay model, ``core.latency.client_round_seconds``, against the deadline);
FedAvg generalizes to partial participation (``fedavg_partial``: survivors
average, dropped clients keep their stale adapter and rejoin from it); and
all masking is exact under full participation, so a dynamic round with
every client present reproduces the static trajectory bit for bit.
"""
from __future__ import annotations

import warnings
from dataclasses import dataclass
from typing import Any, Dict, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..configs.base import ArchConfig, TrainConfig
from ..precision import PrecisionConfig, fake_quant, round_key
from ..models import stack as stack_mod
from ..models.layers import apply_norm, embed, unembed
from ..models.model import IGNORE_ID
from ..models.stack import Runtime, default_train_runtime
from ..optim import Optimizer, apply_updates
from ..sharding.specs import CLIENT_AXIS
from .aggregation import (broadcast_het, fedavg_partial, robust_aggregate,
                          tree_all_finite)
from .defense import corrupt_updates
from .latency import client_round_seconds, workload_tables
from .lora import client_slot_masks
from .split import layers_to_reps


def quantize_activations(s: jax.Array) -> jax.Array:
    """int8 per-token symmetric quantization of split-layer activations —
    a beyond-paper lever on eq. (10): the uplink payload Gamma_s halves
    (bytes_per_activation 2 -> 1 + a negligible per-token scale).

    Straight-through estimator: forward sees the dequantized value, the
    backward pass is the identity (the paper's activation-gradient download
    stays exact).

    Legacy helper: the trainer now routes boundary quantization through
    ``repro.precision.fake_quant`` (traced per-client bit-widths,
    stochastic rounding, error feedback); this stays as the standalone
    per-token reference.  The ``jnp.maximum(scale, 1e-8)`` floor guards
    the all-zero tensor (zero-init LoRA boundary on step 0): without it
    the 0/0 divide turns the whole tensor into NaN."""
    scale = jnp.max(jnp.abs(s), axis=-1, keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    deq = jnp.round(s / scale) * scale
    return s + jax.lax.stop_gradient(deq - s)


def _ce_terms(logits: jax.Array, labels: jax.Array):
    """Token cross-entropy as (sum over labelled tokens, their count) —
    the two partial sums a data-parallel server pass reduces across
    devices before dividing."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, jnp.maximum(labels, 0)[..., None],
                               axis=-1)[..., 0]
    mask = (labels != IGNORE_ID).astype(jnp.float32)
    return jnp.sum((logz - gold) * mask), mask.sum()


def _concat_rows(parts):
    """Per-layer expert row counts of consecutive stack slices, None where
    no slice has a dropless MoE layer."""
    parts = [r for r in parts if r is not None]
    return jnp.concatenate(parts) if parts else None


def _split_rows(out):
    """A client pass's (acts, aux, rows) as ((acts, aux), rows) for
    ``jax.vjp(has_aux=True)``: the counts carry no gradient."""
    acts, aux, rows = out
    return (acts, aux), rows


@jax.tree_util.register_dataclass
@dataclass
class SflState:
    lora_client: Any          # stacked over the client axis K
    lora_server: Any
    opt_client: Any
    opt_server: Any
    step: jax.Array
    # error-feedback accumulators of the quantized split boundary
    # (``PrecisionConfig.error_feedback``): the compression residual of the
    # activation upload / gradient download, re-injected before the next
    # step's quantizer.  ``None`` (the default) keeps the legacy pytree
    # structure — a pre-precision checkpoint restores untouched.
    err_act: Any = None       # (K, b, S, d) f32 or None
    err_grad: Any = None      # (K, b, S, d) f32 or None


@jax.tree_util.register_dataclass
@dataclass
class RoundDynamics:
    """Per-round traced inputs of a dynamic wireless round.

    Every field is optional and, when present, is a traced array — the
    values change round to round with NO retrace.  The pytree *structure*
    (which fields are arrays vs None) must stay constant across the rounds
    of one episode; that is what the single-trace guarantee hangs on.

    Participation / dropout (pick one):
      participation  (K,) 0/1 mask, used as-is;
      deadline_s     scalar round deadline on the client-attributable share
                     T_k = I(T_k^F + T_k^s + T_k^B) + T_k^f, evaluated
                     in a small jitted mask function from the channel state
                     below — a client whose modeled delay exceeds it is
                     dropped for the round.  The resulting mask feeds the
                     SAME main round executable a static round uses (with
                     an all-ones mask), so every round of a trainer —
                     static, faded, dropped, re-allocated — shares one
                     compiled trace and full participation bit-reproduces
                     the static trajectory by construction.

    Channel state (deadline dropout inputs; eqs. 8/10/13/15):
      rates_main / rates_fed   (K,) uplink rates (bps) under this round's
                               fading and the current power/subchannels;
      f_hz / kappa             (K,) client compute capability / cycles-per-FLOP.

    Per-round allocation (from ``SflLLM.allocation_dynamics``; requires a
    capacity envelope, see ``ell_range``/``rank_max``):
      ell / rank       (K,) split layers and LoRA ranks (latency model);
      rep_hi           (K,) int32 split boundaries in repeat units;
      slot_masks       pytree of per-client slot occupancy masks;
      scales           (K,) adapter scales alpha / r_k.

    Boundary precision (``repro.precision``; from ``allocation_dynamics``
    or hand-built):
      act_bits         (K,) f32 per-client activation bit-widths for the
                       split-boundary upload — a traced operand of the
                       same compiled round, so per-round re-allocation
                       can also move each client's precision.  A row of
                       16.0 passes that client's activations through
                       bit-identically (in-graph ``jnp.where`` disarm);
                       ``None`` falls back to the trainer's static bits.

    Outage + HARQ retransmissions (``core.channel`` outage model):
      retx_main / retx_fed  (K,) expected transmission counts E[m] >= 1 per
                            uplink — they inflate the traced delay twin's
                            upload terms, so a client whose retransmissions
                            push T_k past the deadline drops for the round
                            (composition with deadline dropout).  All-ones
                            multiplies by 1.0 exactly (bit-identical to the
                            outage-free trajectory).  Hard outages (all
                            HARQ attempts failed) are expressed through
                            ``participation``, which now COMPOSES with the
                            deadline mask (product) instead of replacing it.

    Fault injection (``faults.inject`` — chaos tests only):
      poison           scalar 0/1; 1 overwrites the post-aggregation server
                       adapter with NaN, deterministically tripping the
                       divergence-rollback sentinel.  0 selects the clean
                       values leaf-for-leaf (``jnp.where`` — bit-exact), so
                       an unpoisoned round of a chaos episode reproduces
                       the fault-free trajectory.
      byzantine        :class:`core.defense.ByzantineOps` — traced per-client
                       corruption of the uploaded adapter updates (sign
                       flip / scale / noise / stale replay), applied inside
                       the round between the scan and aggregation.  The
                       benign operand set is a bit-exact no-op per client.

    Robust aggregation (``core.aggregation``):
      robust           :class:`RobustAggConfig` of traced scalars selecting
                       the Byzantine-tolerant aggregator (norm clip /
                       trimmed mean / median) for this round.  When present
                       the round also emits in-graph anomaly scores
                       (``metrics["anomaly_scores"]``: per-client update
                       norm + cosine distance to the robust aggregate).
                       The disarmed configuration (clip=inf, trim=0,
                       median=0) is bit-identical to ``fedavg_partial``.
    """

    participation: Optional[jax.Array] = None
    rates_main: Optional[jax.Array] = None
    rates_fed: Optional[jax.Array] = None
    f_hz: Optional[jax.Array] = None
    kappa: Optional[jax.Array] = None
    deadline_s: Optional[jax.Array] = None
    ell: Optional[jax.Array] = None
    rank: Optional[jax.Array] = None
    rep_hi: Optional[jax.Array] = None
    slot_masks: Optional[Any] = None
    scales: Optional[jax.Array] = None
    retx_main: Optional[jax.Array] = None
    retx_fed: Optional[jax.Array] = None
    poison: Optional[jax.Array] = None
    robust: Optional[Any] = None
    byzantine: Optional[Any] = None
    act_bits: Optional[jax.Array] = None


class SflLLM:
    """Split-federated LoRA fine-tuning of one ArchConfig model."""

    def __init__(self, cfg: ArchConfig, params: dict,
                 ell_c: Union[int, Sequence[int]],
                 train_cfg: TrainConfig, optimizer: Optimizer,
                 rt: Optional[Runtime] = None,
                 aux_coef: Optional[float] = None,
                 act_quant: bool = False,
                 act_bits: Union[int, Sequence[int], None] = None,
                 mesh=None, donate: bool = True,
                 ranks: Optional[Sequence[int]] = None,
                 ell_range: Optional[Sequence[int]] = None,
                 rank_max: Optional[int] = None):
        self.cfg = cfg
        self.tc = train_cfg
        # default: the fast-path runtime (chunked attention + fused LoRA
        # projections); pass an explicit Runtime to override
        self.rt = default_train_runtime() if rt is None else rt
        self.opt = optimizer
        K = train_cfg.num_clients

        # ---- per-client split points / ranks ----------------------------
        if isinstance(ell_c, (int, np.integer)):
            ells = (int(ell_c),) * K
        else:
            ells = tuple(int(e) for e in ell_c)
            if len(ells) != K:
                raise ValueError(f"{len(ells)} split points for {K} clients")
        self.ell_k = ells
        self.rep_k = tuple(layers_to_reps(cfg, e) for e in ells)
        self.rep_min, self.rep_max = min(self.rep_k), max(self.rep_k)
        self.rank_k = (None if ranks is None
                       else tuple(int(r) for r in ranks))
        if self.rank_k is not None and len(self.rank_k) != K:
            raise ValueError(f"{len(self.rank_k)} ranks for {K} clients")
        self.r_max = max(self.rank_k) if self.rank_k else cfg.lora_rank

        # ---- capacity envelope (per-round traced re-allocation) ---------
        # widen the frozen-weight partition and the adapter rank padding so
        # a later allocation_dynamics() can move every client's (ell_k, r_k)
        # anywhere inside [ell_range] x [1, rank_max] without retracing
        self.dynamic_capacity = ell_range is not None or rank_max is not None
        if ell_range is not None:
            lo, hi = int(min(ell_range)), int(max(ell_range))
            if not 1 <= lo <= hi <= cfg.num_layers:
                raise ValueError(f"ell_range {ell_range} outside "
                                 f"[1, {cfg.num_layers}]")
            self.rep_min = min(self.rep_min, layers_to_reps(cfg, lo))
            self.rep_max = max(self.rep_max, layers_to_reps(cfg, hi))
        if rank_max is not None:
            if self.rank_k is None:
                self.rank_k = (cfg.lora_rank,) * K
            self.r_max = max(self.r_max, int(rank_max))

        # gates are needed whenever any client's boundary sits strictly
        # inside the scanned window (mixed fleet OR widened envelope)
        self.hetero_split = (len(set(self.rep_k)) > 1
                             or self.rep_min != self.rep_max)
        self.hetero_rank = (self.rank_k is not None
                            and len(set(self.rank_k)) > 1)
        pad_rank = self.rank_k is not None and self.r_max > max(self.rank_k)
        self.hetero = self.hetero_split or self.hetero_rank or pad_rank
        # legacy scalar views (homogeneous callers / reports)
        self.ell_c = ells[0] if not self.hetero_split else max(ells)
        self.rep_split = self.rep_max

        self.aux_coef = cfg.router_aux_coef if aux_coef is None else aux_coef

        # ---- boundary precision (repro.precision) -----------------------
        # one typed config on the Runtime is the source of truth; the
        # ``act_bits`` kwarg (int or per-client sequence) overrides its
        # act_bits — e.g. from a HeteroAllocation's per-client ``bits_k``
        prec = getattr(self.rt, "precision", None)
        self.precision: PrecisionConfig = (PrecisionConfig() if prec is None
                                           else prec)
        self.act_quant = bool(act_quant)
        if act_quant:
            warnings.warn(
                "SflLLM(act_quant=True) is deprecated; use "
                "Runtime(precision=PrecisionConfig(act_bits=8)) or the "
                "act_bits kwarg instead", DeprecationWarning, stacklevel=2)
            if act_bits is None and self.precision.act_bits >= 16:
                act_bits = 8
        if act_bits is None:
            bits_k = ((self.precision.act_bits,) * K
                      if self.precision.act_bits < 16 else None)
        elif isinstance(act_bits, (int, np.integer)):
            bits_k = (int(act_bits),) * K
        else:
            bits_k = tuple(int(x) for x in act_bits)
            if len(bits_k) != K:
                raise ValueError(f"{len(bits_k)} act_bits for {K} clients")
        if bits_k is not None and any(x not in (4, 8, 16) for x in bits_k):
            raise ValueError(f"act_bits must be 4, 8 or 16, got {bits_k}")
        # NOTE: an explicit all-16 stays armed (in-graph jnp.where disarm,
        # bit-identical by construction) — that is the tested guarantee;
        # only the *absence* of a request skips the quantizer entirely.
        self.act_bits_k = bits_k
        self._act_bits = (jnp.asarray(bits_k, jnp.float32)
                          if bits_k is not None else None)
        self._grad_bits = (jnp.full((K,), self.precision.grad_bits,
                                    jnp.float32)
                           if self.precision.grad_bits < 16 else None)
        self.mesh = mesh              # optional ("clients",) mesh (launch.mesh)
        if mesh is not None and K % mesh.shape[CLIENT_AXIS]:
            raise ValueError(f"{K} clients do not split over the "
                             f"{mesh.shape[CLIENT_AXIS]}-device client mesh")
        self.donate = donate
        # frozen weights, physically partitioned.  Heterogeneous fleets
        # overlap: clients hold the prefix up to max(ell_k), the server
        # holds from min(ell_k) — each sample crosses at its own boundary.
        # Every jitted call takes ``self.base`` as an ARGUMENT: closed over,
        # the weights would be baked into the executable as constants (a
        # second device copy of the model and a model-sized compile).
        if cfg.prefix and self.hetero:
            raise NotImplementedError(
                "per-client splits and ranks need a config without leading "
                "layers outside its pattern")
        base = {
            "client": {
                "embed": params["embed"],
                "layers": jax.tree.map(lambda v: v[:self.rep_max],
                                       params["layers"]),
            },
            "server": {
                "embed": params["embed"],        # unembedding / LM head
                "layers": jax.tree.map(lambda v: v[self.rep_min:],
                                       params["layers"]),
                "final_norm": params["final_norm"],
            },
        }
        if cfg.prefix:          # the leading layers stay whole on clients
            base["client"]["prefix"] = params["prefix"]
        if mesh is not None:
            from ..sharding.specs import replicated_shardings
            base = jax.device_put(base, replicated_shardings(base, mesh))
        self.base = base

        # ---- hetero bookkeeping: masks, boundaries, adapter scales ------
        # legacy convention keeps the cfg-derived scale; explicit ranks
        # scale each client's adapter by alpha/r_k (and the padded server
        # adapter by alpha/r_max)
        if self.rank_k is not None:
            self._scale_k = tuple(cfg.lora_alpha / r for r in self.rank_k)
            self._server_scale = (cfg.lora_alpha / self.r_max
                                  if self.r_max != cfg.lora_rank else None)
        else:
            self._scale_k = None
            self._server_scale = None
        # uniform non-default scale can stay a static python float
        if self._scale_k is not None and not self.hetero_rank:
            self._scale_k = (None if self._scale_k[0]
                             == cfg.lora_alpha / cfg.lora_rank
                             else self._scale_k[0])
        self._client_masks = None
        if self.hetero:
            ranks_k = self.rank_k or (self.r_max,) * K
            self._client_masks = self._build_client_masks(
                ranks_k, self.rep_k if self.hetero_split else None)
            self._rep_hi = jnp.asarray(self.rep_k, jnp.int32)      # (K,)

        self._round_traces = 0        # host-side retrace counter (tests)
        self._mask_traces = 0         # ditto for the dropout-mask function
        # every jitted entry takes the frozen ``base`` first, the state second
        self._jit_local_step = jax.jit(self._local_step)
        self._jit_eval = jax.jit(self._eval_loss)
        self._jit_round_part = jax.jit(self._train_round_part,
                                       donate_argnums=(1,) if donate else ())
        self._jit_mask = jax.jit(self._dropout_mask,
                                 static_argnums=(10, 11, 12))

    # ------------------------------------------------------------------
    def _build_client_masks(self, ranks, reps, force: bool = False):
        """Slot-mask tree for a per-client (rank, rep) configuration
        against this trainer's capacity envelope — the ONE construction
        both the static closure masks and the per-round traced masks of
        ``allocation_dynamics`` go through, so they can never drift apart:
        abstract template at r_max, truncated to [:rep_max], masked by
        ``core.lora.client_slot_masks``, device-placed next to the stacked
        state when a mesh is set."""
        from ..models.model import abstract_lora
        tmpl = abstract_lora(self.cfg, self.r_max, dtype=jnp.float32)
        client_tmpl = jax.tree.map(      # [:rep_max] on abstract leaves
            lambda v: jax.ShapeDtypeStruct(
                (self.rep_max,) + v.shape[1:], v.dtype), tmpl)
        masks = client_slot_masks(client_tmpl, ranks, reps, force=force)
        if masks is not None and self.mesh is not None:
            from ..sharding.specs import client_array_shardings
            masks = jax.device_put(
                masks, client_array_shardings(masks, self.mesh))
        return masks

    # ------------------------------------------------------------------
    @classmethod
    def from_allocation(cls, prob, alloc, params: dict, optimizer: Optimizer,
                        *, train_cfg: Optional[TrainConfig] = None,
                        dynamic: bool = False, **kw) -> "SflLLM":
        """Build the trainer straight from a resource-allocation decision.

        ``prob``: core.resource.Problem; ``alloc``: an Allocation (global
        pair) or HeteroAllocation (per-client ``ell_k`` / ``rank_k`` from
        ``bcd_minimize_delay_per_client``).  The demo flow is: sample a
        wireless scenario -> BCD -> ``from_allocation`` -> train the fleet.

        ``dynamic=True`` sizes the capacity envelope to the whole search
        space of ``prob`` (every valid split x every candidate rank), so
        per-round drift-triggered re-allocation can move each client's
        (ell_k, r_k) between rounds without a retrace.
        """
        K = len(prob.envs)
        if dynamic:
            from .split import valid_splits
            splits = valid_splits(prob.cfg)
            kw.setdefault("ell_range", (min(splits), max(splits)))
            kw.setdefault("rank_max", max(prob.rank_candidates))
        if train_cfg is None:
            train_cfg = TrainConfig(num_clients=K, batch_size=prob.batch,
                                    local_steps=prob.local_steps)
        ells = np.asarray(getattr(alloc, "ell_k", None)
                          if getattr(alloc, "ell_k", None) is not None
                          else alloc.ell_c).reshape(-1)
        ranks = np.asarray(getattr(alloc, "rank_k", None)
                           if getattr(alloc, "rank_k", None) is not None
                           else alloc.rank).reshape(-1)
        if ells.size == 1:
            ells = np.full(K, ells[0])
        if ranks.size == 1:
            ranks = np.full(K, ranks[0])
        # per-client boundary precision from the allocator: HeteroAllocation
        # carries bits_k, the global Allocation a single act_bits; 16 = off
        bits = getattr(alloc, "bits_k", None)
        if bits is None:
            ab = int(getattr(alloc, "act_bits", 16) or 16)
            if ab < 16:
                bits = np.full(K, ab)
        else:
            bits = np.asarray(bits).reshape(-1)
            if bits.size == 1:
                bits = np.full(K, bits[0])
        if bits is not None:
            kw.setdefault("act_bits", tuple(int(x) for x in bits))
        return cls(prob.cfg, params, tuple(int(e) for e in ells), train_cfg,
                   optimizer, ranks=tuple(int(r) for r in ranks), **kw)

    def init_lora(self, key, dtype=jnp.float32):
        """Template adapter for :meth:`init_state`, padded to max(r_k)."""
        from ..models.model import init_lora_stack
        return init_lora_stack(self.cfg, key, rank=self.r_max, dtype=dtype)

    def init_state(self, lora_template) -> SflState:
        """lora_template: adapter for the FULL stack (models.init_lora_stack).

        The client part is replicated K times (every client starts from the
        same broadcast global adapter, as after an aggregation round).  For
        heterogeneous ranks the template must be padded to max(r_k) —
        :meth:`init_lora` builds one — and each client's dead slots are
        zeroed here so the padded math starts exact."""
        if self.rank_k is not None:
            for path, leaf in jax.tree_util.tree_leaves_with_path(lora_template):
                name = path[-1].key
                r = leaf.shape[1] if name == "a" else leaf.shape[-1]
                if r != self.r_max:
                    raise ValueError(
                        f"template rank {r} != max client rank {self.r_max}"
                        " — build the template with SflLLM.init_lora")
        layers = lora_template["layers"] if self.cfg.prefix else lora_template
        lc = jax.tree.map(lambda v: v[:self.rep_max], layers)
        ls = jax.tree.map(lambda v: v[self.rep_min:], layers)
        if self.cfg.prefix:
            lc = {"prefix": lora_template["prefix"], "layers": lc}
        K = self.tc.num_clients
        lc_k = jax.tree.map(lambda v: jnp.broadcast_to(v, (K,) + v.shape).copy(), lc)
        if self._client_masks is not None:
            lc_k = jax.tree.map(lambda v, m: v * m.astype(v.dtype),
                                lc_k, self._client_masks)
        state = SflState(
            lora_client=lc_k,
            lora_server=ls,
            opt_client=self.opt.init(lc_k),
            opt_server=self.opt.init(ls),
            step=jnp.zeros((), jnp.int32),
        )
        return self.shard_state(state)

    def shard_state(self, state: SflState) -> SflState:
        """Place the state on the client-axis mesh (no-op without a mesh).

        The jitted round follows the committed input shardings, so placing
        the K-stacked client adapter + optimizer leaves as
        ``P("clients", ...)`` makes the whole vmapped client FP/BP run
        data-parallel over devices."""
        if self.mesh is None:
            return state
        from ..sharding.specs import sfl_state_shardings
        return jax.device_put(state, sfl_state_shardings(state, self.mesh))

    # ------------------------------------------------------------------
    def _client_forward(self, lora_c, tokens, frontend_emb, cbase,
                        rep_hi=None, lora_scale=None):
        """One client's FP: embed + layers [0, ell_k) -> activations s_k.
        ``cbase`` is the frozen client base (``self.base["client"]``).

        ``rep_hi`` (heterogeneous splits): the client's own boundary in
        repeat units — the scan runs to max(ell_k) with repeats past the
        boundary gated to identity, so the output IS the split-layer
        activation and client BP past the boundary is masked exactly."""
        cfg, rt = self.cfg, self.rt
        S = tokens.shape[1] + (0 if frontend_emb is None else frontend_emb.shape[1])
        positions = jnp.arange(S, dtype=jnp.int32)
        x = embed(cfg, cbase["embed"], tokens, positions[-tokens.shape[1]:])
        if frontend_emb is not None:
            x = jnp.concatenate([frontend_emb.astype(x.dtype), x], axis=1)
        rows = []
        if cfg.prefix:
            x, r, aux = stack_mod.apply_stack(
                cfg, cbase["prefix"], x, positions=positions,
                lora=lora_c["prefix"], rt=rt, mode="train",
                lora_scale=lora_scale, pattern=cfg.prefix)
            rows.append(r)
            lora_c = lora_c["layers"]
        x, r, a = stack_mod.apply_stack(
            cfg, cbase["layers"], x, positions=positions,
            lora=lora_c, rt=rt, mode="train",
            rep_gate=(None, rep_hi) if rep_hi is not None else None,
            lora_scale=lora_scale)
        rows.append(r)
        return x, (aux + a if cfg.prefix else a), _concat_rows(rows)

    def _server_loss(self, lora_s, acts, labels, sbase, rep_lo=None):
        """Pooled loss on the main server.  acts: (K, b, S, d); ``sbase``
        is the frozen server base (``self.base["server"]``).

        ``rep_lo`` (heterogeneous splits): per-sample entry depth — repeats
        below each sample's boundary pass through as identity, so every
        client's activation is consumed at its own split depth in one
        pooled scan.

        With a client mesh the pooled batch is data-parallel: each device
        runs the server stack on its own clients' rows inside ``shard_map``
        (Pallas kernels cannot be partitioned automatically), and the
        cross-entropy partial sums meet before the divide."""
        if self.mesh is None:
            num, den, aux, rows = self._server_terms(lora_s, acts, labels,
                                                     sbase, rep_lo)
        else:
            def body(lora_s, acts, labels, sbase, rep_lo):
                return tuple(None if t is None else t[None]
                             for t in self._server_terms(
                                 lora_s, acts, labels, sbase, rep_lo))

            C = P(CLIENT_AXIS)
            num, den, aux, rows = jax.shard_map(
                body, mesh=self.mesh, in_specs=(P(), C, C, P(), C),
                out_specs=C, check_vma=False)(lora_s, acts, labels, sbase,
                                              rep_lo)
            num, den, aux = num.sum(), den.sum(), aux.mean()
            rows = None if rows is None else rows.sum(0)
        loss = num / jnp.maximum(den, 1.0)
        return loss + self.aux_coef * aux, (loss, rows)

    def _server_terms(self, lora_s, acts, labels, sbase, rep_lo):
        """Server FP over ``acts``: (CE sum, labelled-token count, aux,
        rows routed to each held expert per dropless MoE layer or None)."""
        cfg, rt = self.cfg, self.rt
        K, b, S, d = acts.shape
        x = acts.reshape(K * b, S, d)
        positions = jnp.arange(S, dtype=jnp.int32)
        with jax.named_scope("sfl.server_stack"):
            x, rows, aux = stack_mod.apply_stack(
                cfg, sbase["layers"], x, positions=positions,
                lora=lora_s, rt=rt, mode="train",
                rep_gate=(rep_lo, None) if rep_lo is not None else None,
                lora_scale=self._server_scale)
        with jax.named_scope("sfl.head"):
            x = apply_norm(cfg, x, sbase["final_norm"])
            logits = unembed(cfg, sbase["embed"], x)
            lbl = labels.reshape(K * b, -1)
            F = logits.shape[1] - lbl.shape[1]
            if F > 0:
                logits = logits[:, F:]
            num, den = _ce_terms(logits, lbl)
        return num, den, aux, rows

    # ------------------------------------------------------------------
    def _local_step(self, base, state: SflState,
                    batches: Dict[str, jax.Array]):
        """One fine-tuning round (steps a-f of Section IV-A).

        batches: tokens (K, b, S), labels (K, b, S), optional frontend_emb.
        """
        return self._step_impl(base, state, batches, None, None)

    def _step_impl(self, base, state: SflState,
                   batches: Dict[str, jax.Array],
                   cfg_dyn: Optional[Dict[str, Any]], part):
        """One local step, optionally under round dynamics.

        ``cfg_dyn`` (dict with ``rep_hi`` / ``slot_masks`` / ``scales``, or
        None) may override the per-client split boundaries / slot masks /
        adapter scales with *traced* arrays (per-round re-allocation);
        ``part`` is the (K,) 0/1 participation mask resolved for the round
        (None = everyone).  With ``cfg_dyn is None and part is None`` this
        is graph-for-graph the legacy static local step.  Every masking op
        is exact under full participation — integer selects and multiplies
        by 1.0 — so an all-ones mask computes exactly the unmasked step.
        """
        tokens, labels = batches["tokens"], batches["labels"]
        fe = batches.get("frontend_emb")
        if part is not None:
            # a dropped client never uploads: its tokens leave the pooled
            # loss (numerator AND denominator) through the label ignore
            # mask, so the server adapter trains on the survivors' pool
            # only and the cotangent of its activation stream is exactly 0
            labels = jnp.where(part.reshape(-1, 1, 1) > 0, labels, IGNORE_ID)

        rep_hi_dyn = cfg_dyn.get("rep_hi") if cfg_dyn is not None else None
        scales_dyn = cfg_dyn.get("scales") if cfg_dyn is not None else None
        masks = (cfg_dyn["slot_masks"]
                 if cfg_dyn is not None
                 and cfg_dyn.get("slot_masks") is not None
                 else self._client_masks)

        # (a) client-side FP, all clients in parallel ----------------------
        # homogeneous fleets keep the legacy vmap signature (bit-identical
        # trace); heterogeneity threads per-client boundaries / adapter
        # scales through the client axis of the same single vmap
        rep_hi = (rep_hi_dyn if rep_hi_dyn is not None
                  else (self._rep_hi if self.hetero_split else None))
        het_split = rep_hi is not None
        scales = self._scale_k
        per_client_scale = isinstance(scales, tuple) or scales_dyn is not None
        if het_split or per_client_scale:
            if scales_dyn is not None:
                sc = scales_dyn
            elif isinstance(scales, tuple):
                sc = jnp.asarray(scales, jnp.float32)
            else:
                sc = None

            in_axes = (0, 0, None if fe is None else 0,
                       0 if het_split else None,
                       0 if sc is not None else None)

            def fwd_k(ls, tok, f, rh, s, cbase):
                def cf(lora_c, tok, f, rh, s):
                    return self._client_forward(
                        lora_c, tok, f, cbase, rep_hi=rh,
                        lora_scale=s if s is not None else scales)
                return jax.vmap(cf, in_axes=in_axes)(ls, tok, f, rh, s)

            client_args = (tokens, fe, rep_hi, sc)
        else:
            def fwd_k(ls, tok, f, cbase):
                def cf(lora_c, tok, f):
                    return self._client_forward(lora_c, tok, f, cbase,
                                                lora_scale=scales)
                if f is None:
                    return jax.vmap(lambda l, t: cf(l, t, None))(ls, tok)
                return jax.vmap(cf)(ls, tok, f)

            client_args = (tokens, fe)
        if self.mesh is not None:
            # each device runs its own clients' FP (and, through the vjp,
            # their BP) — an explicit shard_map, because Pallas kernels
            # cannot be partitioned automatically
            n_in = len(client_args)
            fwd_k = jax.shard_map(
                fwd_k, mesh=self.mesh,
                in_specs=(P(CLIENT_AXIS),) * (n_in + 1) + (P(),),
                out_specs=P(CLIENT_AXIS), check_vma=False)

        def fwd(ls):
            # inside the vjp, so client BP (client_vjp below) carries the
            # scope as transpose(jvp(sfl.client))
            with jax.named_scope("sfl.client"):
                return fwd_k(ls, *client_args, base["client"])

        (acts, client_aux), client_vjp, client_rows = jax.vjp(
            lambda ls: _split_rows(fwd(ls)), state.lora_client, has_aux=True)

        # boundary quantization (repro.precision): the uploaded payload is
        # the (de)quantized activation — applied OUTSIDE the client vjp,
        # so the server's g_acts later feeds client_vjp unchanged, which
        # IS the straight-through estimator.  ``act_bits`` is a traced
        # (K,) operand (per-round re-allocation moves it with no retrace);
        # rows at 16.0 select the raw activation bit-identically.
        bits_dyn = cfg_dyn.get("act_bits") if cfg_dyn is not None else None
        act_bits = bits_dyn if bits_dyn is not None else self._act_bits
        new_err_act, new_err_grad = state.err_act, state.err_grad
        key_a = key_g = None
        if self.precision.stochastic_rounding and (
                act_bits is not None or self._grad_bits is not None):
            base_key = round_key(self.precision.rng_seed, state.step)
            key_a = jax.random.fold_in(base_key, 0)
            key_g = jax.random.fold_in(base_key, 1)
        if act_bits is not None:
            with jax.named_scope("sfl.boundary"):
                acts, new_err_act = fake_quant(acts, act_bits, key=key_a,
                                               err=state.err_act)

        # (b) upload (s_k, y_k) — wireless; modeled in core.latency --------
        # (c,d) server FP + BP on the pooled activations --------------------
        rep_lo = None
        if het_split:
            b = tokens.shape[1]
            rep_lo = jnp.repeat(rep_hi - self.rep_min, b)  # (K*b,)
        grad_fn = jax.value_and_grad(self._server_loss, argnums=(0, 1),
                                     has_aux=True)
        (total, (loss, server_rows)), (g_server, g_acts) = grad_fn(
            state.lora_server, acts, labels, base["server"], rep_lo)

        # (e) download dL/ds_k; (f) client-side BP --------------------------
        # the downloaded gradient is quantized the same way the uploaded
        # activation was (static config-wide grad_bits, per-client scale)
        if self._grad_bits is not None:
            with jax.named_scope("sfl.boundary"):
                g_acts, new_err_grad = fake_quant(g_acts, self._grad_bits,
                                                  key=key_g,
                                                  err=state.err_grad)
        # client-side MoE aux loss contributes through the aux cotangent
        # (masked per client under partial participation)
        aux_seed = jnp.full_like(client_aux, self.aux_coef)
        if part is not None:
            aux_seed = aux_seed * part
        (g_client,) = client_vjp((g_acts, aux_seed))

        with jax.named_scope("sfl.optimizer"):
            upd_s, opt_s = self.opt.update(g_server, state.opt_server,
                                           state.lora_server)
            upd_c, opt_c = self.opt.update(g_client, state.opt_client,
                                           state.lora_client)
            if masks is not None:
                # masked updates: dead rows/cols of the padded adapters stay
                # exactly zero no matter what the optimizer does with eps /
                # weight decay
                upd_c = jax.tree.map(lambda u, m: u * m.astype(u.dtype),
                                     upd_c, masks)
            if part is not None:
                # a dropped client's adapter AND optimizer moments freeze for
                # the round: zero grads alone would still decay Adam moments
                pcol = lambda v: part.reshape((-1,) + (1,) * (v.ndim - 1))
                upd_c = jax.tree.map(lambda u: u * pcol(u).astype(u.dtype),
                                     upd_c)
                opt_c = jax.tree.map(
                    lambda n, o: n if n.ndim == 0
                    else jnp.where(pcol(n) > 0, n, o),
                    opt_c, state.opt_client)
                # an empty round (every client past the deadline) freezes the
                # server as well — nobody uploaded, nothing trained
                any_p = part.sum() > 0
                upd_s = jax.tree.map(
                    lambda u: jnp.where(any_p, u, jnp.zeros_like(u)), upd_s)
                opt_s = jax.tree.map(lambda n, o: jnp.where(any_p, n, o),
                                     opt_s, state.opt_server)
            new = SflState(
                lora_client=apply_updates(state.lora_client, upd_c),
                lora_server=apply_updates(state.lora_server, upd_s),
                opt_client=opt_c,
                opt_server=opt_s,
                step=state.step + 1,
                err_act=new_err_act,
                err_grad=new_err_grad,
            )
        metrics = {"loss": loss, "total": total}
        rows = _concat_rows([None if client_rows is None
                             else client_rows.sum(0), server_rows])
        if rows is not None:
            # rows routed to each held expert, per dropless MoE layer
            metrics["expert_rows"] = rows
        return new, metrics

    # ------------------------------------------------------------------
    def _aggregate(self, state: SflState, weights: jax.Array) -> SflState:
        """Federated-server round (eq. 7), fully in-graph: one weighted
        tensordot reduction over the stacked client axis + broadcast.
        Heterogeneous fleets aggregate slot-wise over each slot's owners
        and re-truncate on broadcast (fedavg_het/broadcast_het; exact
        fedavg_stacked when every client is full-rank/full-depth)."""
        state, _ = self._aggregate_impl(state, weights, None,
                                        self._client_masks)
        return state

    def _aggregate_impl(self, state: SflState, weights: jax.Array, part,
                        masks, robust=None, ref=None):
        """Eq. 7 under (optional) partial participation: the global adapter
        is the survivors' weighted average (``fedavg_partial``); a dropped
        client missed the whole round — broadcast included — so it keeps
        its stale adapter bit-exactly and rejoins from it next round.
        If EVERY client dropped, the weight mass is zero and every client
        keeps its state (no aggregation happened).

        ``robust`` (a traced :class:`RobustAggConfig`) swaps the plain
        average for the Byzantine-tolerant aggregator and emits per-client
        anomaly scores against ``ref`` (the pre-round stacked adapters);
        the disarmed configuration selects the plain aggregate bit-exactly
        (``core.aggregation.robust_aggregate``).  Returns
        ``(state, scores-or-None)``."""
        if robust is not None:
            global_c, scores = robust_aggregate(
                state.lora_client, ref, weights, part, masks, robust)
        else:
            global_c = fedavg_partial(state.lora_client, weights, part,
                                      masks)
            scores = None
        lc_k = broadcast_het(global_c, self.tc.num_clients, masks)
        if part is not None:
            pcol = lambda v: part.reshape((-1,) + (1,) * (v.ndim - 1))
            lc_k = jax.tree.map(
                lambda n, o: jnp.where(pcol(n) > 0, n, o),
                lc_k, state.lora_client)
        return SflState(lora_client=lc_k, lora_server=state.lora_server,
                        opt_client=state.opt_client,
                        opt_server=state.opt_server,
                        step=state.step, err_act=state.err_act,
                        err_grad=state.err_grad), scores

    def aggregate(self, state: SflState, sample_counts) -> SflState:
        """FedAvg client adapters + broadcast (eq. 7)."""
        return self._aggregate(state,
                               jnp.asarray(list(sample_counts), jnp.float32))

    # ------------------------------------------------------------------
    def _train_round_part(self, base, state: SflState, round_batches,
                          weights, part, cfg_dyn, poison=None, robust=None,
                          byz=None):
        """The one compiled global round every caller runs: scan + in-graph
        FedAvg with the (K,) participation mask — and optionally a whole
        re-allocated per-client configuration — as traced inputs.  Static
        rounds pass an all-ones mask; faded / dropped / re-allocated rounds
        pass this round's values.  Same structure => ONE trace for the
        entire episode, and full participation is bit-identical to a static
        round because it IS the same executable.

        round_batches: tokens (I, K, b, S), labels (I, K, b, S), optional
        frontend_emb (I, K, b, F, d); weights: (K,) sample counts.

        Divergence rollback: after the scan + aggregation the whole new
        state is checked all-finite in-graph (``tree_all_finite``); a
        NaN/inf anywhere (an exploded update, or an injected ``poison``)
        rolls the ENTIRE round back — every leaf, optimizer moments and
        step counter included, via ``jnp.where`` per leaf — so a diverged
        round is bit-identical to the last-good state (the all-dropped
        identity, reached through a different trigger).  A finite round
        commits through ``where(True, new, old)``, which is bit-exact, so
        the sentinel never perturbs a healthy trajectory.

        Byzantine round structure (both optional, fixed per episode):
        ``byz`` (:class:`core.defense.ByzantineOps`) corrupts the uploaded
        adapter updates between the scan and aggregation — traced
        per-client operands, benign values a bit-exact no-op; ``robust``
        (:class:`RobustAggConfig`) swaps FedAvg for the in-graph
        Byzantine-tolerant aggregator and adds per-client anomaly scores
        to the metrics (update norm + cosine distance to the robust
        aggregate), measured against the pre-round broadcast adapters."""
        self._round_traces += 1       # trace-time only: retrace telemetry
        masks = (cfg_dyn["slot_masks"]
                 if cfg_dyn is not None
                 and cfg_dyn.get("slot_masks") is not None
                 else self._client_masks)
        ref = state.lora_client       # pre-round (post-broadcast) adapters
        new, metrics = jax.lax.scan(
            lambda st, b: self._step_impl(base, st, b, cfg_dyn, part),
            state, round_batches)
        with jax.named_scope("sfl.fedavg"):
            if byz is not None:
                # corrupted uploads: the radio payload between client and
                # federated server — optimizer moments stay the client's own
                new = SflState(
                    lora_client=corrupt_updates(new.lora_client, ref, byz),
                    lora_server=new.lora_server, opt_client=new.opt_client,
                    opt_server=new.opt_server, step=new.step,
                    err_act=new.err_act, err_grad=new.err_grad)
            new, scores = self._aggregate_impl(new, weights, part, masks,
                                               robust, ref)
        if poison is not None:
            # deterministic fault injection: poison > 0 NaNs the aggregated
            # server adapter; poison == 0 keeps the clean values bit-exactly
            new = SflState(
                lora_client=new.lora_client,
                lora_server=jax.tree.map(
                    lambda v: jnp.where(poison > 0, jnp.full_like(v, jnp.nan),
                                        v), new.lora_server),
                opt_client=new.opt_client, opt_server=new.opt_server,
                step=new.step, err_act=new.err_act, err_grad=new.err_grad)
        with jax.named_scope("sfl.commit"):
            finite = tree_all_finite(new)
            state = jax.tree.map(lambda n, o: jnp.where(finite, n, o),
                                 new, state)
        metrics = dict(metrics, participation=part, rolled_back=~finite)
        if scores is not None:
            metrics["anomaly_scores"] = scores
        return state, metrics

    def _dropout_mask(self, rates_main, rates_fed, f_hz, kappa, ell, rank,
                      deadline_s, retx_main, retx_fed, act_bits,
                      b: int, local_steps: int, seq_len: int):
        """Deadline-aware straggler dropout, in-graph: the traced twin of
        the Section V per-client delay (``core.latency.client_round_seconds``)
        against the round deadline — with the upload terms inflated by the
        expected HARQ transmission counts when an outage model is active.
        Jitted separately from the main round (static_argnums on the
        shapes) so deadline rounds feed the SAME main executable as static
        rounds — the mask is data, not structure."""
        self._mask_traces += 1
        tables = workload_tables(self.cfg, seq_len)
        t_k = client_round_seconds(tables, ell, rank, f_hz, kappa,
                                   rates_main, rates_fed, b, local_steps,
                                   retx_main=retx_main, retx_fed=retx_fed,
                                   act_bits=act_bits)
        return (t_k <= deadline_s).astype(jnp.float32)

    def _participation_for(self, dyn: RoundDynamics, batches):
        """Resolve the round's (K,) mask.  An explicit ``participation``
        and a ``deadline_s`` COMPOSE (product of the two masks — a client
        must both survive the deadline and not be in hard outage); either
        alone is used as-is, neither means all ones.  Multiplying by an
        all-ones mask is exact, so composing a never-outaged explicit mask
        with the deadline mask reproduces the deadline-only trajectory."""
        K = self.tc.num_clients
        explicit = (None if dyn.participation is None
                    else jnp.asarray(dyn.participation, jnp.float32))
        if dyn.deadline_s is None:
            return explicit if explicit is not None \
                else jnp.ones(K, jnp.float32)
        if (dyn.rates_main is None or dyn.rates_fed is None
                or dyn.f_hz is None or dyn.kappa is None):
            raise ValueError("deadline dropout needs rates_main, rates_fed,"
                             " f_hz and kappa in RoundDynamics")
        I, _, b, S = batches["tokens"].shape
        ell = (dyn.ell if dyn.ell is not None
               else jnp.asarray(self.ell_k, jnp.int32))
        rank = (dyn.rank if dyn.rank is not None
                else jnp.asarray(self.rank_k or (self.cfg.lora_rank,) * K,
                                 jnp.float32))
        bits = dyn.act_bits if dyn.act_bits is not None else self._act_bits
        part = self._jit_mask(dyn.rates_main, dyn.rates_fed, dyn.f_hz,
                              dyn.kappa, ell, rank, dyn.deadline_s,
                              dyn.retx_main, dyn.retx_fed, bits,
                              int(b), int(I), int(S))
        return part if explicit is None else part * explicit

    def train_round(self, state: SflState, round_batches, sample_counts,
                    dynamics: Optional[RoundDynamics] = None):
        """Run one jitted global round.  Returns (state, metrics) with
        metrics["loss"] of shape (I,) and metrics["participation"] of
        shape (K,).  State buffers are donated when the runtime was built
        with donate=True — do not reuse the input state.

        ``dynamics``: per-round traced inputs (:class:`RoundDynamics`) for
        time-varying episodes — fading channel state, deadline dropout /
        participation, per-round re-allocation.  All rounds of a trainer
        run ONE compiled graph (mask + optional config arrays are traced
        inputs; a static round is the all-ones mask), so mixing static and
        dynamic rounds never retraces as long as the re-allocation arrays
        are either always or never supplied.

        Profiler spans (no-ops unless a trace is running): ``sfl.put``
        (batches, weights and the participation mask to the device) and
        ``sfl.enqueue`` (the compiled round's dispatch)."""
        with jax.profiler.TraceAnnotation("sfl.put"):
            batches = {k: jnp.asarray(v) for k, v in round_batches.items()
                       if v is not None}
            weights = jnp.asarray(list(sample_counts), jnp.float32)
            if self.mesh is not None:
                from ..sharding.specs import round_batch_shardings
                batches = jax.device_put(
                    batches, round_batch_shardings(batches, self.mesh))
            dyn = RoundDynamics() if dynamics is None else dynamics
            part = self._participation_for(dyn, batches)
            cfg_dyn = None
            if (dyn.rep_hi is not None or dyn.slot_masks is not None
                    or dyn.scales is not None or dyn.act_bits is not None):
                cfg_dyn = {"rep_hi": dyn.rep_hi, "slot_masks": dyn.slot_masks,
                           "scales": dyn.scales, "act_bits": dyn.act_bits}
            state = self._ensure_err_state(
                state, batches["tokens"].shape[-2:],
                batches.get("frontend_emb"),
                armed_act=(self._act_bits is not None
                           or dyn.act_bits is not None))
            if self.mesh is not None:
                from ..sharding.specs import round_dynamics_shardings
                part, cfg_dyn = jax.device_put(
                    (part, cfg_dyn),
                    round_dynamics_shardings((part, cfg_dyn), self.mesh))
        with jax.profiler.TraceAnnotation("sfl.enqueue"):
            return self._jit_round_part(self.base, state, batches, weights,
                                        part, cfg_dyn, dyn.poison, dyn.robust,
                                        dyn.byzantine)

    def allocation_dynamics(self, ell_k, rank_k,
                            bits_k=None) -> Dict[str, Any]:
        """A per-client allocation decision as RoundDynamics kwargs (``ell``
        / ``rank`` / ``rep_hi`` / ``slot_masks`` / ``scales``, plus
        ``act_bits`` when ``bits_k`` is given), expressed against this
        trainer's capacity envelope.  Swapping these between rounds
        re-points the existing slot-mask machinery at the new (ell_k, r_k)
        with NO retrace; the trainer must have been built with a wide
        enough envelope (``ell_range`` / ``rank_max``, e.g. via
        ``from_allocation(..., dynamic=True)``).  ``bits_k`` needs no
        envelope at all — the bit-width is a traced operand of the
        quantizer, not a shape."""
        K = self.tc.num_clients
        ells = tuple(int(e) for e in np.asarray(ell_k).reshape(-1))
        ranks = tuple(int(r) for r in np.asarray(rank_k).reshape(-1))
        if len(ells) != K or len(ranks) != K:
            raise ValueError(f"{len(ells)} splits / {len(ranks)} ranks "
                             f"for {K} clients")
        reps = tuple(layers_to_reps(self.cfg, e) for e in ells)
        if max(reps) > self.rep_max or min(reps) < self.rep_min:
            raise ValueError(
                f"split points {ells} leave the capacity envelope "
                f"reps [{self.rep_min}, {self.rep_max}] — build the trainer "
                "with ell_range (from_allocation(dynamic=True))")
        if max(ranks) > self.r_max:
            raise ValueError(f"rank {max(ranks)} > capacity r_max "
                             f"{self.r_max} — build with rank_max")
        masks = self._build_client_masks(ranks, reps, force=True)
        out = dict(
            ell=jnp.asarray(ells, jnp.int32),
            rank=jnp.asarray(ranks, jnp.float32),
            rep_hi=jnp.asarray(reps, jnp.int32),
            slot_masks=masks,
            scales=jnp.asarray([self.cfg.lora_alpha / r for r in ranks],
                               jnp.float32),
        )
        if bits_k is not None:
            bits = tuple(int(x) for x in np.asarray(bits_k).reshape(-1))
            if len(bits) != K:
                raise ValueError(f"{len(bits)} bit-widths for {K} clients")
            if any(x not in (4, 8, 16) for x in bits):
                raise ValueError(f"bits_k must be 4, 8 or 16, got {bits}")
            out["act_bits"] = jnp.asarray(bits, jnp.float32)
        return out

    def _ensure_err_state(self, state: SflState, bs, frontend_emb, *,
                          armed_act: bool) -> SflState:
        """Lazily attach the error-feedback accumulators (host-side, before
        the first compile) when the config asks for them.  Idempotent, and
        a no-op without ``error_feedback`` — the legacy pytree structure is
        untouched, so pre-precision episodes keep their compiled trace."""
        if not self.precision.error_feedback:
            return state
        armed_grad = self._grad_bits is not None
        if not armed_act and not armed_grad:
            return state
        b, S = int(bs[0]), int(bs[1])
        if frontend_emb is not None:
            S += int(frontend_emb.shape[-2])
        shape = (self.tc.num_clients, b, S, self.cfg.d_model)
        ea, eg = state.err_act, state.err_grad
        if armed_act and ea is None:
            ea = jnp.zeros(shape, jnp.float32)
        if armed_grad and eg is None:
            eg = jnp.zeros(shape, jnp.float32)
        if ea is state.err_act and eg is state.err_grad:
            return state
        return self.shard_state(SflState(
            lora_client=state.lora_client, lora_server=state.lora_server,
            opt_client=state.opt_client, opt_server=state.opt_server,
            step=state.step, err_act=ea, err_grad=eg))

    # ------------------------------------------------------------------
    def local_step(self, state, batches):
        state = self._ensure_err_state(
            state, batches["tokens"].shape[-2:],
            batches.get("frontend_emb"),
            armed_act=self._act_bits is not None)
        return self._jit_local_step(self.base, state, batches)

    def train(self, state: SflState, data_iter, *, global_rounds: int,
              sample_counts, log_every: int = 0, callback=None):
        """E global rounds x I local steps (Algorithm 1) — one jitted call
        per global round (scan over local steps + in-graph FedAvg)."""
        from ..data.pipeline import stack_rounds

        history = []
        for e in range(global_rounds):
            round_batches = stack_rounds(data_iter, self.tc.local_steps)
            state, metrics = self.train_round(state, round_batches,
                                              sample_counts)
            losses = [float(x) for x in jax.device_get(metrics["loss"])]
            for i, loss in enumerate(losses):
                history.append(loss)
                if log_every and len(history) % log_every == 0:
                    print(f"round {e} step {i} loss {loss:.4f}")
            if callback is not None:
                callback(state, history)
        return state, history

    # ------------------------------------------------------------------
    def _eval_loss(self, base, state: SflState, batch):
        """Validation loss through client 0's adapter (post-aggregation all
        clients share the slots client 0 owns)."""
        lora_c0 = jax.tree.map(lambda v: v[0], state.lora_client)
        scales = self._scale_k
        scale0 = scales[0] if isinstance(scales, tuple) else scales
        rep_hi0 = jnp.int32(self.rep_k[0]) if self.hetero_split else None
        acts, _, _ = self._client_forward(lora_c0, batch["tokens"],
                                          batch.get("frontend_emb"),
                                          base["client"], rep_hi=rep_hi0,
                                          lora_scale=scale0)
        rep_lo = None
        if self.hetero_split:
            b = batch["tokens"].shape[0]
            rep_lo = jnp.full((b,), self.rep_k[0] - self.rep_min, jnp.int32)
        num, den, _, _ = self._server_terms(state.lora_server, acts[None],
                                            batch["labels"][None],
                                            base["server"], rep_lo)
        return num / jnp.maximum(den, 1.0)

    def eval_loss(self, state, batch):
        return self._jit_eval(self.base, state, batch)


# ---------------------------------------------------------------------------
# centralized baseline (Section VII-B comparison)
# ---------------------------------------------------------------------------

class CentralizedLoRA:
    """Pooled-data LoRA fine-tuning — the paper's comparison baseline."""

    def __init__(self, cfg: ArchConfig, params: dict, train_cfg: TrainConfig,
                 optimizer: Optimizer, rt: Optional[Runtime] = None,
                 donate: bool = True):
        from ..models.model import loss_fn

        rt = default_train_runtime() if rt is None else rt
        self.cfg, self.tc, self.rt, self.opt = cfg, train_cfg, rt, optimizer
        self.params = params

        def step(lora, opt_state, batch):
            (total, m), grads = jax.value_and_grad(
                lambda l: loss_fn(cfg, params, l, batch, rt=rt),
                has_aux=True)(lora)
            upd, opt_state = optimizer.update(grads, opt_state, lora)
            return apply_updates(lora, upd), opt_state, m

        def round_(carry, round_batches):
            def body(c, batch):
                lora, opt_state = c
                lora, opt_state, m = step(lora, opt_state, batch)
                return (lora, opt_state), m
            return jax.lax.scan(body, carry, round_batches)

        self._jit_step = jax.jit(step)
        self._jit_train_round = jax.jit(
            round_, donate_argnums=(0,) if donate else ())

    def init_state(self, lora):
        # fresh buffers: train_round donates state, which must never delete
        # the caller's template arrays
        lora = jax.tree.map(jnp.copy, lora)
        return lora, self.opt.init(lora)

    def step(self, lora, opt_state, batch):
        return self._jit_step(lora, opt_state, batch)

    def train_round(self, state, round_batches):
        """One compiled round: scan over the leading step axis of
        round_batches (tokens/labels (I, B, S)).  state = (lora, opt_state);
        input buffers are donated."""
        batches = {k: jnp.asarray(v) for k, v in round_batches.items()
                   if v is not None}
        return self._jit_train_round(state, batches)
