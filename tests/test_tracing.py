"""Profiler spans of the training loop and named phases of the compiled
SFL round, on a tiny SflLLM on CPU.

``Trainer.fit`` and ``SflLLM.train_round`` mark their host work with
``jax.profiler`` spans; the round's phases of Algorithm 1 are
``jax.named_scope``s, which reach the compiled program as metadata only.
Neither may change what the round computes."""
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import TrainConfig, get_arch
from repro.core.sfl import SflLLM
from repro.launch.engine import SflRound, Trainer
from repro.models.model import init_lora_stack, init_params
from repro.optim import adamw

K, B, S, I, ROUNDS = 2, 2, 32, 2, 3
ROUND_SPANS = ("train.round", "train.dispatch", "sfl.put", "sfl.enqueue",
               "train.pull", "train.callback", "train.checkpoint")
PHASES = ("sfl.client", "sfl.boundary", "sfl.server_stack", "sfl.head",
          "sfl.optimizer", "sfl.fedavg", "sfl.commit")


def _sfl(**kw):
    cfg = get_arch("gpt2-s").reduced(num_layers=2, d_model=64, vocab=512)
    tc = TrainConfig(num_clients=K, batch_size=B, local_steps=I)
    return SflLLM(cfg, init_params(cfg, jax.random.key(0)), ell_c=1,
                  train_cfg=tc, optimizer=adamw(1e-3), **kw)


def _data():
    rng = np.random.default_rng(0)
    while True:
        ids = rng.integers(0, 512, (K, B, S + 1), np.int32)
        yield {"tokens": ids[..., :S], "labels": ids[..., 1:]}


def _fit(sfl, **kw):
    trainer = Trainer(SflRound(sfl, [B] * K), local_steps=I,
                      callback=lambda e, state, hist: None, **kw)
    state = sfl.init_state(init_lora_stack(sfl.cfg, jax.random.key(1)))
    return trainer.fit(state, _data(), global_rounds=ROUNDS)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Three rounds of ``Trainer.fit`` under the profiler (after one
    untraced fit that compiles): the named host spans, [(name, start,
    end)] in start order, and the fit's losses and state."""
    from jax.profiler import ProfileData

    tmp = tmp_path_factory.mktemp("trace")
    sfl = _sfl()
    _fit(sfl)
    with jax.profiler.trace(str(tmp / "prof")):
        state, hist = _fit(sfl, checkpoint_path=str(tmp / "ck.msgpack"),
                           checkpoint_every=1)
    jax.block_until_ready(state)
    path = glob.glob(str(tmp / "prof/plugins/profile/*/*.xplane.pb"))[0]
    host = next(p for p in ProfileData.from_file(path).planes
                if p.name == "/host:CPU")
    spans = sorted(((e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for line in host.lines for e in line.events
                    if e.name.startswith(("train.", "sfl."))),
                   key=lambda s: s[1])
    return {"spans": spans, "state": state, "losses": hist.losses}


@pytest.mark.parametrize("name", ROUND_SPANS)
def test_span_once_per_round(traced, name):
    assert [s[0] for s in traced["spans"]].count(name) == ROUNDS


def test_stage_span_before_each_round(traced):
    stages = [s for s in traced["spans"] if s[0] == "train.stage"]
    rounds = [s for s in traced["spans"] if s[0] == "train.round"]
    # one before the first round, then one prefetch inside every round
    # but the last
    assert len(stages) == ROUNDS
    assert stages[0][2] <= rounds[0][1]
    for st, rd in zip(stages[1:], rounds):
        assert rd[1] <= st[1] and st[2] <= rd[2]


def test_spans_nest_and_order_within_a_round(traced):
    spans = traced["spans"]

    def inside(name, outer):
        return [s for s in spans
                if s[0] == name and outer[1] <= s[1] and s[2] <= outer[2]]

    for rd in (s for s in spans if s[0] == "train.round"):
        (dispatch,) = inside("train.dispatch", rd)
        (put,) = inside("sfl.put", dispatch)
        (enqueue,) = inside("sfl.enqueue", dispatch)
        (pull,) = inside("train.pull", rd)
        assert put[2] <= enqueue[1]
        assert dispatch[2] <= pull[1]


def test_profiler_changes_nothing_computed(traced):
    state, hist = _fit(_sfl())
    assert hist.losses == traced["losses"]
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(traced["state"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.fixture(scope="module")
def lowered_round():
    """The round's lowered text with locations; 8-bit boundary activations
    so the boundary phase is in the program."""
    sfl = _sfl(act_bits=8)
    state = sfl.init_state(init_lora_stack(sfl.cfg, jax.random.key(1)))
    batches = {k: jnp.zeros((I, K, B, S), jnp.int32)
               for k in ("tokens", "labels")}
    ones = jnp.ones((K,), jnp.float32)
    low = sfl._jit_round_part.lower(sfl.base, state, batches, ones, ones,
                                    None)
    return low.as_text(debug_info=True)


@pytest.mark.parametrize("phase", PHASES)
def test_round_carries_phase(lowered_round, phase):
    assert f"{phase}/" in lowered_round or f"{phase})" in lowered_round
