"""The paper's core invariants: SFL == centralized LoRA training (server
adapter exactly; client adapters via the FedAvg lr/K relation), and
aggregation follows eq. 7."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import TrainConfig, get_arch
from repro.core.aggregation import fedavg
from repro.core.lora import split_tree
from repro.core.sfl import CentralizedLoRA, SflLLM
from repro.optim import sgd, adamw
from repro import models as M


def _setup(key, arch="gpt2-s", K=3, b=2, S=16, layers=4):
    cfg = get_arch(arch).reduced(num_layers=layers)
    params = M.init_params(cfg, key)
    lora = M.init_lora_stack(cfg, jax.random.key(7))
    tokens = jax.random.randint(key, (K, b, S), 0, cfg.vocab_size)
    return cfg, params, lora, {"tokens": tokens, "labels": tokens}


def test_sfl_equals_centralized_sgd(key):
    K, eta = 3, 0.1
    cfg, params, lora, batches = _setup(key, K=K)
    tc = TrainConfig(num_clients=K, batch_size=2, local_steps=1)
    sfl = SflLLM(cfg, params, ell_c=2, train_cfg=tc, optimizer=sgd(eta))
    st, m = sfl.local_step(sfl.init_state(lora), batches)
    st = sfl.aggregate(st, [1.0] * K)

    cen = CentralizedLoRA(cfg, params, tc, sgd(eta))
    l0, opt = cen.init_state(lora)
    K_, b, S = batches["tokens"].shape
    pooled = {k: v.reshape(K_ * b, S) for k, v in batches.items()}
    l1, opt, m2 = cen.step(l0, opt, pooled)

    assert abs(float(m["loss"]) - float(m2["loss"])) < 1e-5

    cli_c, srv_c = split_tree(l1, 2)
    # server adapter: exact
    for a, b_ in zip(jax.tree.leaves(srv_c), jax.tree.leaves(st.lora_server)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=1e-6)
    # aggregated client adapter: init + centralized_update / K
    cli_i, _ = split_tree(lora, 2)
    exp = jax.tree.map(lambda i, c: i + (c - i) / K, cli_i, cli_c)
    got = jax.tree.map(lambda v: v[0], st.lora_client)
    for a, b_ in zip(jax.tree.leaves(exp), jax.tree.leaves(got)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=1e-6)


@pytest.mark.parametrize("split", [1, 2, 3])
def test_split_point_invariance_of_loss(key, split):
    """The split point must not change the computed loss (only WHERE
    compute happens)."""
    cfg, params, lora, batches = _setup(key)
    tc = TrainConfig(num_clients=3, batch_size=2, local_steps=1)
    sfl = SflLLM(cfg, params, ell_c=split, train_cfg=tc, optimizer=sgd(0.1))
    _, m = sfl.local_step(sfl.init_state(lora), batches)
    if not hasattr(test_split_point_invariance_of_loss, "_ref"):
        test_split_point_invariance_of_loss._ref = float(m["loss"])
    assert abs(float(m["loss"])
               - test_split_point_invariance_of_loss._ref) < 1e-5


def test_fedavg_weighted(key):
    t1 = {"a": jnp.ones((2, 2)), "b": jnp.zeros(3)}
    t2 = {"a": 3 * jnp.ones((2, 2)), "b": 6 * jnp.ones(3)}
    avg = fedavg([t1, t2], [1.0, 3.0])      # weights normalize to 1/4, 3/4
    np.testing.assert_allclose(np.asarray(avg["a"]), 2.5 * np.ones((2, 2)))
    np.testing.assert_allclose(np.asarray(avg["b"]), 4.5 * np.ones(3))


def test_sfl_training_decreases_loss(key):
    cfg, params, lora, _ = _setup(key)
    K, b, S = 3, 2, 16
    tc = TrainConfig(num_clients=K, batch_size=b, local_steps=4)
    sfl = SflLLM(cfg, params, ell_c=2, train_cfg=tc, optimizer=adamw(3e-3))
    state = sfl.init_state(lora)
    tokens = jax.random.randint(key, (K, b, S), 0, cfg.vocab_size)
    batches = {"tokens": tokens, "labels": tokens}   # memorize one batch
    data = iter(lambda: batches, None)
    state, hist = sfl.train(state, data, global_rounds=3,
                            sample_counts=[1.0] * K)
    assert hist[-1] < hist[0] - 0.1


def test_server_never_sees_tokens(key):
    """Structural privacy check: the server loss function consumes
    activations + labels only (its signature has no token input)."""
    import inspect

    sig = inspect.signature(SflLLM._server_loss)
    assert "tokens" not in sig.parameters
    assert list(sig.parameters)[:4] == ["self", "lora_s", "acts", "labels"]


def test_eval_loss_finite(key):
    cfg, params, lora, batches = _setup(key)
    tc = TrainConfig(num_clients=3, batch_size=2, local_steps=1)
    sfl = SflLLM(cfg, params, ell_c=2, train_cfg=tc, optimizer=sgd(0.1))
    state = sfl.init_state(lora)
    val = {"tokens": batches["tokens"][0], "labels": batches["labels"][0]}
    assert np.isfinite(float(sfl.eval_loss(state, val)))


def _constant_bytes(stablehlo_text: str) -> int:
    """Bytes held by the constants of a lowered StableHLO module."""
    import re

    itemsize = {"f32": 4, "i32": 4, "ui32": 4, "bf16": 2, "f16": 2,
                "i8": 1, "i1": 1}
    total = 0
    for t in re.findall(r"stablehlo\.constant dense(?:_resource)?<.*?>\s*"
                        r":\s*tensor<([^>]*)>", stablehlo_text):
        *dims, dt = t.split("x")
        total += int(np.prod([int(d) for d in dims])) * itemsize.get(dt, 8)
    return total


@pytest.mark.parametrize("ell_c", [2, (1, 2, 3)])
def test_round_takes_frozen_weights_as_arguments(key, ell_c):
    """The compiled round receives the frozen base as arguments: closed
    over, the weights would be baked into the executable as constants (a
    second device copy of the model and a model-sized compile)."""
    K, b, S, I = 3, 2, 16, 2
    cfg, params, lora, _ = _setup(key, K=K, b=b, S=S)
    tc = TrainConfig(num_clients=K, batch_size=b, local_steps=I)
    sfl = SflLLM(cfg, params, ell_c=ell_c, train_cfg=tc,
                 optimizer=adamw(1e-3), donate=False)
    state = sfl.init_state(lora)
    tok = jnp.zeros((I, K, b, S), jnp.int32)
    lowered = sfl._jit_round_part.lower(
        sfl.base, state, {"tokens": tok, "labels": tok}, jnp.ones(K),
        jnp.ones(K), None)
    weights = sum(v.nbytes for v in jax.tree.leaves(params))
    consts = _constant_bytes(lowered.as_text())
    assert consts < weights / 100, (consts, weights)
    n_args = len(jax.tree.leaves(lowered.args_info))
    assert n_args >= len(jax.tree.leaves(sfl.base)), n_args
