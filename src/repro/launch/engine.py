"""Unified round-based training engine.

Every trainer in the repo — the paper's SflLLM (Algorithm 1), the
centralized LoRA baseline, and the datacenter pod lowering — executes the
same outer shape: E global rounds, each a single *compiled* call that scans
the I local steps (plus, for SFL, in-graph FedAvg).  This module owns that
outer loop once:

* round loop with prefetch: the next round's stacked batches are built on
  the host while the device executes the current round (jax async
  dispatch — we only block on the loss floats after staging the next xs);
* logging / loss history;
* checkpoint hooks (``checkpoint.save_pytree`` every N rounds);
* profiler spans on the host's work of each round (``train.round`` and,
  inside it, ``train.dispatch``, ``train.stage``, ``train.pull``,
  ``train.callback``, ``train.checkpoint``): ``jax.profiler`` annotations,
  which do nothing unless a trace is running;
* modeled per-round wall clock over the wireless network (core.latency
  eq. 16-17), accumulated next to the measured wall clock so runs report
  both "what the hardware did" and "what the paper's network would take".

The three trainers plug in via small adapters exposing
``run_round(state, round_batches) -> (state, metrics)`` where
``metrics["loss"]`` has shape (I,).
"""
from __future__ import annotations

import dataclasses
import os
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterator, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.latency import client_round_seconds_host
from ..data.pipeline import stack_rounds


# ---------------------------------------------------------------------------
# trainer adapters
# ---------------------------------------------------------------------------

class SflRound:
    """Adapter: core.sfl.SflLLM — compiled scan + in-graph FedAvg."""

    def __init__(self, sfl, sample_counts):
        self.sfl = sfl
        self.sample_counts = list(sample_counts)

    def run_round(self, state, round_batches, dynamics=None):
        return self.sfl.train_round(state, round_batches, self.sample_counts,
                                    dynamics=dynamics)

    def checkpoint_payload(self, state) -> dict:
        return {"lora_server": state.lora_server,
                "lora_client": state.lora_client}


class CentralizedRound:
    """Adapter: core.sfl.CentralizedLoRA — compiled scan over pooled
    batches (I, B, S).  state = (lora, opt_state)."""

    def __init__(self, cen):
        self.cen = cen

    def run_round(self, state, round_batches):
        return self.cen.train_round(state, round_batches)

    def checkpoint_payload(self, state) -> dict:
        return {"lora": state[0]}


class PodRound:
    """Adapter: the datacenter lowering — one LoRA train step sharded over
    an N-device ("data", "model") mesh, scanned I times per round.

    state = (lora, opt_state); params stay frozen and are passed once."""

    def __init__(self, cfg, params, rt, optimizer, mesh, *,
                 donate: bool = True):
        from ..models.stack import default_train_runtime
        from ..sharding import (lora_shardings, opt_state_shardings,
                                params_shardings, stacked_batch_shardings)
        from .steps import make_train_step

        rt = default_train_runtime() if rt is None else rt
        self.optimizer = optimizer
        self.mesh = mesh
        step = make_train_step(cfg, rt, optimizer)

        def round_(params, carry, round_batches):
            def body(c, batch):
                lora, opt_state = c
                lora, opt_state, m = step(params, lora, opt_state, batch)
                return (lora, opt_state), m
            return jax.lax.scan(body, carry, round_batches)

        self._round = jax.jit(round_, donate_argnums=(1,) if donate else ())
        self._params = jax.device_put(params, params_shardings(params, mesh))
        self._lora_sh = lambda t: lora_shardings(t, mesh)
        self._opt_sh = lambda t: opt_state_shardings(t, None, mesh)
        self._batch_sh = lambda t: stacked_batch_shardings(t, mesh)

    def init_state(self, lora):
        opt_state = self.optimizer.init(lora)
        return (jax.device_put(lora, self._lora_sh(lora)),
                jax.device_put(opt_state, self._opt_sh(opt_state)))

    def run_round(self, state, round_batches):
        batches = {k: jnp.asarray(v) for k, v in round_batches.items()}
        batches = jax.device_put(batches, self._batch_sh(batches))
        return self._round(self._params, state, batches)

    def checkpoint_payload(self, state) -> dict:
        return {"lora": state[0]}


# ---------------------------------------------------------------------------
# modeled wall clock (paper Section V)
# ---------------------------------------------------------------------------

def modeled_round_seconds(report: Dict[str, Any], local_steps: int) -> float:
    """Per-global-round modeled delay from a core.latency.latency_report:
    I local rounds (eq. 16) + the federated LoRA upload (eq. 15)."""
    return local_steps * report["t_local"] + report["t3"]


def modeled_total_seconds(prob, alloc) -> float:
    """Total modeled training delay of an allocation (eq. 17 with E(r)) —
    the quantity benchmarks sweep.  Dispatches to the per-client objective
    when the allocation carries ``ell_k``/``rank_k``."""
    from ..core.resource import total_delay
    return total_delay(prob, alloc)


def allocation_round_latency(prob, alloc) -> Dict[str, Any]:
    """latency_report for a resource-allocation decision — homogeneous or
    per-client — ready for ``Trainer(round_latency=...)``: the compiled
    rounds then accumulate the wireless wall clock this allocation models,
    so a run reports both what the hardware did and what the paper's
    network would take for THIS fleet."""
    from ..core.latency import latency_report, latency_report_het
    K = len(prob.envs)
    rates_m = alloc.rates_main(prob.sys_cfg, prob.envs)
    rates_f = alloc.rates_fed(prob.sys_cfg, prob.envs)
    e_rounds = prob.e_model(int(alloc.rank))
    if getattr(alloc, "ell_k", None) is not None:
        e_rounds = float(np.mean([prob.e_model(int(r))
                                  for r in alloc.rank_k]))
        return latency_report_het(
            prob.cfg, prob.sys_cfg, prob.envs, rates_m, rates_f,
            alloc.ell_k, alloc.rank_k, prob.seq_len, prob.batch,
            prob.local_steps, e_rounds)
    return latency_report(
        prob.cfg, prob.sys_cfg, prob.envs, rates_m, rates_f,
        int(alloc.ell_c), int(alloc.rank), prob.seq_len, prob.batch,
        prob.local_steps, e_rounds)


# ---------------------------------------------------------------------------
# dynamic wireless rounds: fading -> deadline dropout -> drift re-allocation
# ---------------------------------------------------------------------------

class WirelessDynamics:
    """Round-by-round wireless evolution for the compiled round engine.

    Owns the host side of a time-varying episode; the numbers it produces
    enter the jitted round as *traced* inputs (core.sfl.RoundDynamics), so
    the whole episode — every fading draw, dropout pattern and re-allocated
    (ell_k, r_k) — runs on ONE compiled trace:

    * block fading: ``core.channel.FadingProcess`` (AR(1) in dB around the
      sampled average gains; ``fade_rho=0`` = i.i.d. per-round draws);
    * per-round rates: the current allocation's subchannels/powers
      re-evaluated under the faded gains;
    * straggler dropout: a round deadline on the client-attributable delay
      share T_k = I(T_k^F + T_k^s + T_k^B) + T_k^f — the mask itself is
      computed in-graph from the traced channel state;
    * drift-triggered re-allocation: when the modeled delay of the current
      allocation under this round's channel exceeds (1 + drift_threshold) x
      its delay at (re)allocation time, ``bcd_minimize_delay_per_client``
      re-runs warm-started from the previous HeteroAllocation (monotone:
      never worse than keeping it), and the clients pick up their new
      (ell_k, r_k) through the slot-mask machinery with no retrace.

    * outages + HARQ retransmissions (``core.channel`` outage model): with
      ``outage_snr_db`` set, each uplink's per-transmission outage
      probability follows Rayleigh fast fading around this round's block
      average SNR; the expected (truncated-geometric) transmission count
      E[m] inflates the traced delay twin's upload terms — stragglers now
      include retransmission victims, composing with the deadline — and a
      client whose ``max_harq`` attempts ALL fail is in hard outage for
      the round (explicit participation 0, drawn from a dedicated RNG so
      disabling outages never perturbs the fading stream).

    Knobs:
      fade_std_db      lognormal block-fading std in dB (paper-style 4-8);
      fade_rho         AR(1) round-to-round fading correlation in [0, 1);
      deadline_s       absolute round deadline in seconds (None = off);
      deadline_factor  alternative: deadline = factor x max_k T_k evaluated
                       at the last (re)allocation — re-bases on re-allocation;
      drift_threshold  relative modeled-delay drift that triggers
                       re-allocation (None = static allocation);
      outage_snr_db    per-transmission outage SNR threshold in dB
                       (None = outage model off: RoundDynamics keeps the
                       exact pre-outage traced structure);
      max_harq         HARQ attempt cap m >= 1;
      outage_rng       seed/Generator for the hard-outage Bernoulli draws.

    Byzantine robustness (``defense``: a ``core.defense.DefenseConfig``):
    every round then runs the in-graph robust aggregator
    (``core.aggregation.robust_aggregate`` — norm clip / trimmed mean /
    median as traced scalars) and emits per-client anomaly scores; a
    host-side ``ReputationTracker`` EWMAs the scores and quarantines
    repeatedly-flagged clients for Q rounds by zeroing their
    participation — composing MULTIPLICATIVELY with deadline-straggler
    dropout and hard-outage masks.  The mask is already traced data, so
    quarantining (and releasing) never recompiles; with the aggregator
    knobs disarmed (clip=inf, trim=0, median off) the rounds are
    bit-identical to a defense-free episode.

    Fault-injection hooks (``faults.inject.TrainingFaults`` drives these;
    all are traced DATA, so flipping them mid-episode never retraces):
      outage_override  None, or per-round outage probability override
                       (scalar or (K,)) replacing the channel-derived p;
      poison_next      None (no sentinel input in the trace), or bool —
                       True NaNs the next round's aggregated server adapter
                       in-graph, deterministically exercising divergence
                       rollback; auto-resets to False after firing.
      byzantine_ops    None, or a host dict of per-client corruption
                       operands (sign / scale / noise_std / replay + seed)
                       entering every round as a traced
                       ``core.defense.ByzantineOps`` — armed before round
                       1 by ``TrainingFaults.arm_byzantine`` so the traced
                       structure is fixed up front; benign values are a
                       bit-exact no-op.
    """

    def __init__(self, prob, alloc, sfl, *, fade_std_db: float = 4.0,
                 fade_rho: float = 0.0, deadline_s: Optional[float] = None,
                 deadline_factor: Optional[float] = None,
                 drift_threshold: Optional[float] = None,
                 max_sweeps: int = 2, rng=0,
                 outage_snr_db: Optional[float] = None, max_harq: int = 4,
                 outage_rng=0, defense=None):
        from ..core.channel import FadingProcess
        from ..core.latency import workload_tables
        from ..core.resource import as_hetero, total_delay

        self.prob = prob
        self.alloc = as_hetero(prob, alloc)
        self.sfl = sfl
        self.fading = FadingProcess(prob.envs, std_db=fade_std_db,
                                    rho=fade_rho, rng=rng)
        self.deadline_factor = deadline_factor
        self.drift_threshold = drift_threshold
        self.max_sweeps = max_sweeps
        self._total_delay = total_delay
        self.outage_snr_db = outage_snr_db
        if max_harq < 1:
            raise ValueError(f"max_harq must be >= 1, got {max_harq}")
        self.max_harq = int(max_harq)
        self.outage_rng = (np.random.default_rng(outage_rng)
                           if isinstance(outage_rng, int) else outage_rng)
        self.outage_override = None     # faults.inject: per-round p override
        self.poison_next: Optional[bool] = None  # faults.inject: NaN poke
        self.byzantine_ops = None       # faults.inject: corruption operands
        self._round_idx = 0             # byzantine noise-key cursor
        self.defense = defense
        self.tracker = None
        if defense is not None:
            from ..core.defense import ReputationTracker
            self.tracker = ReputationTracker(len(prob.envs), defense)
        if drift_threshold is not None:
            # fail fast: a drift-triggered re-allocation may pick ANY
            # (ell, rank) in prob's search space — a trainer whose capacity
            # envelope does not cover it would crash rounds into the episode
            from ..core.split import layers_to_reps, valid_splits
            splits = valid_splits(prob.cfg)
            reps = [layers_to_reps(prob.cfg, e)
                    for e in (min(splits), max(splits))]
            if (min(reps) < sfl.rep_min or max(reps) > sfl.rep_max
                    or max(prob.rank_candidates) > sfl.r_max):
                raise ValueError(
                    "re-allocation can leave the trainer's capacity "
                    "envelope — build it with SflLLM.from_allocation(..., "
                    "dynamic=True) or a wide enough ell_range/rank_max")
        self._tables = workload_tables(prob.cfg, prob.seq_len)
        self.ref_delay = total_delay(prob, self.alloc)
        # only a re-allocating episode threads the per-client configuration
        # as traced arrays; with a static allocation the trainer's closure
        # config already matches, so the episode runs the SAME executable a
        # plain static trainer uses (all-ones mask == bit-identical rounds)
        self._cfg_arrays = (
            sfl.allocation_dynamics(self.alloc.ell_k, self.alloc.rank_k,
                                    bits_k=getattr(self.alloc, "bits_k",
                                                   None))
            if drift_threshold is not None else {})
        self.deadline_s = deadline_s
        if deadline_factor is not None:
            if deadline_s is not None:
                raise ValueError("pass deadline_s OR deadline_factor")
            self._rebase_deadline(prob.envs)

    # -- deadline re-basing: factor x slowest client at allocation time ----
    def _client_seconds(self, envs, retx_main=None, retx_fed=None
                        ) -> np.ndarray:
        rates_m = self.alloc.rates_main(self.prob.sys_cfg, envs)
        rates_f = self.alloc.rates_fed(self.prob.sys_cfg, envs)
        t = client_round_seconds_host(
            self._tables, self.alloc.ell_k, self.alloc.rank_k,
            np.array([e.f_hz for e in envs]),
            np.array([e.kappa for e in envs]),
            rates_m, rates_f, self.prob.batch, self.prob.local_steps,
            retx_main=retx_main, retx_fed=retx_fed,
            act_bits=getattr(self.alloc, "bits_k", None))
        return np.asarray(t)

    def _rebase_deadline(self, envs) -> None:
        self.deadline_s = float(self.deadline_factor
                                * self._client_seconds(envs).max())

    # ------------------------------------------------------------------
    def round_dynamics(self):
        """Advance one round; returns (RoundDynamics, info dict)."""
        from ..core.resource import bcd_minimize_delay_per_client
        from ..core.sfl import RoundDynamics

        envs_r = self.fading.step()
        # with_envs keeps the channel-independent workload caches warm
        # across rounds (the re-allocation sweeps hit them hundreds of
        # times); only the channel-dependent pair cache resets
        prob_r = self.prob.with_envs(envs_r)
        delay = self._total_delay(prob_r, self.alloc)
        info = {"modeled_delay": float(delay), "realloc": False}
        if (self.drift_threshold is not None
                and delay > (1.0 + self.drift_threshold) * self.ref_delay):
            self.alloc, _ = bcd_minimize_delay_per_client(
                prob_r, warm_start=self.alloc, max_sweeps=self.max_sweeps)
            self.ref_delay = self._total_delay(prob_r, self.alloc)
            self._cfg_arrays = self.sfl.allocation_dynamics(
                self.alloc.ell_k, self.alloc.rank_k,
                bits_k=getattr(self.alloc, "bits_k", None))
            if self.deadline_factor is not None:
                self._rebase_deadline(envs_r)
            info["realloc"] = True
            info["modeled_delay"] = float(self.ref_delay)

        sys_cfg = self.prob.sys_cfg
        rates_m = self.alloc.rates_main(sys_cfg, envs_r)
        rates_f = self.alloc.rates_fed(sys_cfg, envs_r)

        # -- outage + HARQ: per-link E[m] and hard-outage survival ---------
        retx_m = retx_f = survival = None
        if self.outage_snr_db is not None or self.outage_override is not None:
            from ..core.channel import (expected_transmissions,
                                        outage_probability, residual_outage)
            K = len(envs_r)
            if self.outage_override is not None:
                p_m = np.broadcast_to(
                    np.asarray(self.outage_override, float), (K,))
                p_f = p_m
            else:
                snr_th = 10.0 ** (self.outage_snr_db / 10.0)
                noise = sys_cfg.noise_psd_w_hz
                bw_m = np.maximum(self.alloc.bw_main(sys_cfg), 1e-30)
                bw_f = np.maximum(self.alloc.bw_fed(sys_cfg), 1e-30)
                snr_m = (self.alloc.power_main / bw_m / noise
                         * np.array([e.gain_main for e in envs_r]))
                snr_f = (self.alloc.power_fed / bw_f / noise
                         * np.array([e.gain_fed for e in envs_r]))
                p_m = outage_probability(snr_m, snr_th)
                p_f = outage_probability(snr_f, snr_th)
            retx_m = expected_transmissions(p_m, self.max_harq
                                            ).astype(np.float32)
            retx_f = expected_transmissions(p_f, self.max_harq
                                            ).astype(np.float32)
            u = self.outage_rng.uniform(size=(K, 2))
            hard = ((u[:, 0] < residual_outage(p_m, self.max_harq))
                    | (u[:, 1] < residual_outage(p_f, self.max_harq)))
            survival = (~hard).astype(np.float32)
            info["hard_outages"] = hard.astype(int).tolist()

        # -- quarantine: the reputation tracker's mask composes with every
        # other dropout source (product of 0/1 masks); it rides the SAME
        # traced explicit-participation input outages use, so an episode
        # with defense on still runs one compiled round
        explicit = survival
        if self.tracker is not None:
            qmask = self.tracker.mask()
            info["quarantined"] = (1 - qmask).astype(int).tolist()
            explicit = qmask if explicit is None else explicit * qmask

        t_k = self._client_seconds(envs_r, retx_m, retx_f)
        if self.deadline_s is not None:
            # f32 compare, matching the in-graph mask bit for bit
            part = (t_k <= np.float32(self.deadline_s)).astype(float)
        else:
            part = np.ones(len(envs_r))
        if explicit is not None:
            part = part * explicit     # compose: straggler AND outage AND
        info["participation"] = part.astype(int).tolist()   # quarantine
        info["round_seconds"] = self._round_seconds(envs_r, rates_m, rates_f,
                                                    part)

        # poison sentinel: only a chaos episode (poison_next armed to a
        # bool before round 1) carries the traced scalar; it auto-disarms
        # after firing so exactly one round is poisoned per arm
        poison = None
        if self.poison_next is not None:
            poison = jnp.float32(1.0 if self.poison_next else 0.0)
            self.poison_next = False

        # robust aggregation + byzantine corruption: constant *structure*
        # per episode (defense / arm_byzantine fixed before round 1), with
        # every value a traced array — no retrace when knobs change
        robust = (None if self.defense is None
                  else self.defense.robust_config())
        byz = None
        if self.byzantine_ops is not None:
            from ..core.defense import byzantine_ops_arrays
            byz = byzantine_ops_arrays(self.byzantine_ops, self._round_idx)
        self._round_idx += 1

        dyn = RoundDynamics(
            rates_main=jnp.asarray(rates_m, jnp.float32),
            rates_fed=jnp.asarray(rates_f, jnp.float32),
            f_hz=jnp.asarray([e.f_hz for e in envs_r], jnp.float32),
            kappa=jnp.asarray([e.kappa for e in envs_r], jnp.float32),
            deadline_s=(None if self.deadline_s is None
                        else jnp.float32(self.deadline_s)),
            retx_main=(None if retx_m is None
                       else jnp.asarray(retx_m, jnp.float32)),
            retx_fed=(None if retx_f is None
                      else jnp.asarray(retx_f, jnp.float32)),
            participation=(None if explicit is None
                           else jnp.asarray(explicit, jnp.float32)),
            poison=poison,
            robust=robust,
            byzantine=byz,
            **self._cfg_arrays)
        return dyn, info

    # -- anomaly-score feedback (Trainer.fit calls this after each round) --
    def observe_scores(self, scores: Dict[str, Any], participation) -> None:
        """Feed one round's in-graph anomaly scores to the reputation
        tracker (no-op without a defense).  ``participation`` is the
        round's realized (K,) mask — non-participants never update their
        reputation, so a quarantined client's frozen (zero) update cannot
        launder its standing."""
        if self.tracker is None:
            return
        self.tracker.observe(scores["update_norm"], scores["cos_dist"],
                             participation)

    def _round_seconds(self, envs, rates_m, rates_f, part) -> float:
        """Modeled wall clock of this round: survivors' eq. 16-17 terms (the
        server proceeds at the deadline without the stragglers); an empty
        round costs the waited-out deadline."""
        from ..core.latency import (het_local_round_latency, t_lora_upload)

        surv = [k for k in range(len(envs)) if part[k] > 0]
        if not surv:
            return float(self.deadline_s or 0.0)
        sws = [self.prob.sw(int(self.alloc.ell_k[k]),
                            int(self.alloc.rank_k[k])) for k in surv]
        t_local = het_local_round_latency(
            sws, [envs[k] for k in surv], [rates_m[k] for k in surv],
            self.prob.sys_cfg, self.prob.batch)
        t3 = max(t_lora_upload(sw, rates_f[k]) for sw, k in zip(sws, surv))
        return float(self.prob.local_steps * t_local + t3)

    # -- episode checkpoint cursor (Trainer.fit kill/resume) ---------------
    def cursor(self) -> dict:
        """JSON-able snapshot of all host-side episode state: RNG cursors,
        the current (possibly re-allocated) HeteroAllocation, the drift
        reference delay and the (possibly re-based) deadline.  Restoring it
        makes the resumed round sequence bit-identical to an uninterrupted
        run (fault-injection hooks are transient and NOT checkpointed)."""
        a = self.alloc
        return {
            "fading": self.fading.get_state(),
            "outage_rng": self.outage_rng.bit_generator.state,
            "ref_delay": float(self.ref_delay),
            "deadline_s": (None if self.deadline_s is None
                           else float(self.deadline_s)),
            "round_idx": int(self._round_idx),
            "defense": (None if self.tracker is None
                        else self.tracker.state()),
            "alloc": {
                "assign_main": np.asarray(a.assign_main).tolist(),
                "assign_fed": np.asarray(a.assign_fed).tolist(),
                "power_main": np.asarray(a.power_main).tolist(),
                "power_fed": np.asarray(a.power_fed).tolist(),
                "ell_c": int(a.ell_c),
                "rank": int(a.rank),
                "ell_k": np.asarray(a.ell_k).tolist(),
                "rank_k": np.asarray(a.rank_k).tolist(),
                "act_bits": int(getattr(a, "act_bits", 16)),
                "bits_k": (None if getattr(a, "bits_k", None) is None
                           else np.asarray(a.bits_k).tolist()),
            },
        }

    def restore_cursor(self, c: dict) -> None:
        from ..core.resource import HeteroAllocation
        self.fading.set_state(c["fading"])
        self.outage_rng.bit_generator.state = c["outage_rng"]
        self.ref_delay = float(c["ref_delay"])
        self.deadline_s = (None if c["deadline_s"] is None
                           else float(c["deadline_s"]))
        self._round_idx = int(c.get("round_idx", 0))
        if self.tracker is not None and c.get("defense") is not None:
            self.tracker.load_state(c["defense"])
        a = c["alloc"]
        self.alloc = HeteroAllocation(
            assign_main=np.asarray(a["assign_main"], int),
            assign_fed=np.asarray(a["assign_fed"], int),
            power_main=np.asarray(a["power_main"], float),
            power_fed=np.asarray(a["power_fed"], float),
            ell_c=int(a["ell_c"]), rank=int(a["rank"]),
            act_bits=int(a.get("act_bits", 16)),
            ell_k=np.asarray(a["ell_k"], int),
            rank_k=np.asarray(a["rank_k"], int),
            bits_k=(None if a.get("bits_k") is None
                    else np.asarray(a["bits_k"], int)))
        self._cfg_arrays = (
            self.sfl.allocation_dynamics(self.alloc.ell_k, self.alloc.rank_k,
                                         bits_k=getattr(self.alloc, "bits_k",
                                                        None))
            if self.drift_threshold is not None else {})


# ---------------------------------------------------------------------------
# the driver
# ---------------------------------------------------------------------------

@dataclass
class TrainHistory:
    losses: List[float] = field(default_factory=list)
    round_losses: List[float] = field(default_factory=list)   # mean per round
    wall_seconds: float = 0.0
    modeled_seconds: float = 0.0          # wireless-network wall clock
    steps_per_sec: float = 0.0
    participation: List[List[int]] = field(default_factory=list)  # per round
    realloc_rounds: List[int] = field(default_factory=list)
    modeled_delays: List[float] = field(default_factory=list)  # total T per rnd
    rolled_back_rounds: List[int] = field(default_factory=list)  # divergence
    # per-round in-graph anomaly scores ({"update_norm": [...K], "cos_dist":
    # [...K]}) and 0/1 quarantine flags — populated when the episode runs a
    # robust-aggregation defense (JSON-able: they ride episode checkpoints)
    anomaly_scores: List[Dict[str, List[float]]] = field(default_factory=list)
    quarantined: List[List[int]] = field(default_factory=list)
    # per round, (local step, dropless MoE layer, held expert): the
    # (token, choice) rows routed to each held expert (MoE configs)
    expert_rows: List[List[List[List[int]]]] = field(default_factory=list)


class Trainer:
    """Round-loop driver all trainers plug into.

    algo            adapter with run_round(state, round_batches)
    local_steps     I — batches stacked per compiled round
    log_every       print every N rounds (0 = silent)
    round_latency   optional core.latency.latency_report dict; accumulates
                    the modeled wireless wall clock per round
    dynamics        optional WirelessDynamics — per-round fading, deadline
                    dropout and drift re-allocation threaded into the
                    compiled round as traced inputs (SflRound only); the
                    modeled wall clock then follows each round's actual
                    faded channel instead of a static report
    checkpoint_path/checkpoint_every
                    save algo.checkpoint_payload(state) every N rounds
    episode_path/episode_every
                    full-fidelity episode checkpoint every N rounds: device
                    state + round cursor + history + the dynamics cursor
                    (fading/outage RNG, allocation, deadline) in ONE atomic
                    file — ``fit(..., resume=True)`` continues a killed
                    episode bit-identically (same data_iter seed required:
                    the consumed rounds are re-drawn and discarded)
    callback        callback(round_idx, state, history) after each round
    """

    def __init__(self, algo, *, local_steps: int, log_every: int = 0,
                 round_latency: Optional[Dict[str, Any]] = None,
                 dynamics: Optional[WirelessDynamics] = None,
                 checkpoint_path: str = "", checkpoint_every: int = 0,
                 episode_path: str = "", episode_every: int = 0,
                 callback: Optional[Callable] = None):
        self.algo = algo
        self.local_steps = local_steps
        self.log_every = log_every
        self.round_latency = round_latency
        self.dynamics = dynamics
        self.checkpoint_path = checkpoint_path
        self.checkpoint_every = checkpoint_every
        self.episode_path = episode_path
        self.episode_every = episode_every
        self.callback = callback

    # ------------------------------------------------------------------
    def fit(self, state, data_iter: Iterator[Dict], *, global_rounds: int,
            resume: bool = False):
        history = TrainHistory()
        start_round = 0
        if resume and self.episode_path and os.path.exists(self.episode_path):
            from ..checkpoint import restore_episode
            state, meta = restore_episode(self.episode_path, state)
            start_round = int(meta["round"])
            h = meta.get("history", {})
            for f in dataclasses.fields(TrainHistory):
                if f.name in h:
                    setattr(history, f.name, h[f.name])
            if self.dynamics is not None and meta.get("dynamics") is not None:
                self.dynamics.restore_cursor(meta["dynamics"])
        per_round = (modeled_round_seconds(self.round_latency,
                                           self.local_steps)
                     if self.round_latency else 0.0)
        prev_wall = history.wall_seconds
        t0 = time.perf_counter()
        # replay the consumed data stream so round start_round sees exactly
        # the batches it would have in the uninterrupted run
        for _ in range(start_round):
            stack_rounds(data_iter, self.local_steps)
        with jax.profiler.TraceAnnotation("train.stage"):
            staged = stack_rounds(data_iter, self.local_steps)
        for e in range(start_round, global_rounds):
            with jax.profiler.StepTraceAnnotation("train.round", step_num=e):
                if self.dynamics is not None:
                    dyn, info = self.dynamics.round_dynamics()
                    kw = {"dynamics": dyn}
                else:
                    info, kw = None, {}
                with jax.profiler.TraceAnnotation("train.dispatch"):
                    state, metrics = self.algo.run_round(state, staged, **kw)
                if e + 1 < global_rounds:       # prefetch while the device runs
                    with jax.profiler.TraceAnnotation("train.stage"):
                        staged = stack_rounds(data_iter, self.local_steps)
                rb = (metrics.get("rolled_back")
                      if isinstance(metrics, dict) else None)
                # the host's waits on the round just dispatched: its losses
                # and its rollback flag
                rows = (metrics.get("expert_rows")
                        if isinstance(metrics, dict) else None)
                with jax.profiler.TraceAnnotation("train.pull"):
                    loss, rows = jax.device_get((metrics["loss"], rows))
                    rolled_back = rb is not None and bool(jax.device_get(rb))
                if rows is not None:
                    history.expert_rows.append(np.asarray(rows).tolist())
                losses = np.asarray(loss, np.float64).reshape(-1)
                history.losses.extend(float(x) for x in losses)
                history.round_losses.append(float(losses.mean()))
                if rolled_back:
                    history.rolled_back_rounds.append(e)
                scores = (metrics.get("anomaly_scores")
                          if isinstance(metrics, dict) else None)
                if scores is not None:
                    s_host = {k: np.asarray(jax.device_get(v),
                                            np.float64).tolist()
                              for k, v in scores.items()}
                    history.anomaly_scores.append(s_host)
                    if info is not None:
                        # close the loop: this round's scores update client
                        # reputations, which shape the NEXT round's mask
                        self.dynamics.observe_scores(s_host,
                                                     info["participation"])
                if info is not None and "quarantined" in info:
                    history.quarantined.append(info["quarantined"])
                if info is not None:
                    history.modeled_seconds += info["round_seconds"]
                    history.participation.append(info["participation"])
                    history.modeled_delays.append(info["modeled_delay"])
                    if info["realloc"]:
                        history.realloc_rounds.append(e)
                else:
                    history.modeled_seconds += per_round
                if self.log_every and (e + 1) % self.log_every == 0:
                    msg = (f"round {e + 1}/{global_rounds}  "
                           f"loss {losses[-1]:.4f}")
                    if per_round or info is not None:
                        msg += f"  modeled {history.modeled_seconds:.1f}s"
                    if info is not None:
                        msg += f"  clients {sum(info['participation'])}/" \
                               f"{len(info['participation'])}"
                        if info["realloc"]:
                            msg += "  [re-allocated]"
                    print(msg)
                if (self.checkpoint_path and self.checkpoint_every
                        and (e + 1) % self.checkpoint_every == 0):
                    with jax.profiler.TraceAnnotation("train.checkpoint"):
                        self._save(state)
                if (self.episode_path and self.episode_every
                        and (e + 1) % self.episode_every == 0):
                    history.wall_seconds = (prev_wall
                                            + time.perf_counter() - t0)
                    with jax.profiler.TraceAnnotation("train.checkpoint"):
                        self._save_episode(state, e + 1, history)
                if self.callback is not None:
                    with jax.profiler.TraceAnnotation("train.callback"):
                        self.callback(e, state, history)
        history.wall_seconds = prev_wall + (time.perf_counter() - t0)
        steps = len(history.losses)
        if history.wall_seconds > 0:
            history.steps_per_sec = steps / history.wall_seconds
        if self.checkpoint_path and not self.checkpoint_every:
            with jax.profiler.TraceAnnotation("train.checkpoint"):
                self._save(state)
        return state, history

    def _save(self, state) -> None:
        from ..checkpoint import save_pytree
        save_pytree(self.checkpoint_path,
                    self.algo.checkpoint_payload(state))

    def _save_episode(self, state, round_idx: int, history) -> None:
        from ..checkpoint import save_episode
        # block so the saved device state is the state AT this round
        state = jax.block_until_ready(state)
        meta = {"round": int(round_idx),
                "history": dataclasses.asdict(history),
                "dynamics": (None if self.dynamics is None
                             else self.dynamics.cursor())}
        save_episode(self.episode_path, state, meta)
