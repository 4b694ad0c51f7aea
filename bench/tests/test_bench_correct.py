"""``correct`` at a tiny size on the CPU: a whole run of the tiny cell,
without the look for a chip, agrees with the plain reference; with the
timed path broken underneath (a round that returns its state unchanged,
half of every batch left out) the same run comes out not correct; and the
control, the reference computed in bfloat16 in the program's place, fails
the limits."""
import bench_tiny  # noqa: F401 — puts bench/ and src/ on the path
import jax.numpy as jnp
import pytest

import spec


@pytest.fixture
def root(tmp_path, monkeypatch):
    bench_tiny.patch(monkeypatch)
    return bench_tiny.make_root(tmp_path)


def test_train_run_agrees_with_reference(root, capsys):
    out = bench_tiny.run_cell(root, bench_tiny.TRAIN, capsys)
    assert out["correct"] is True, out["checks"]
    assert out["metrics"]["train_tokens_per_s"]["value"] > 0
    assert list(out)[-1] == "checks"


def _state_unchanged(monkeypatch):
    from repro.core import sfl

    real = sfl.SflLLM._step_impl

    def step(self, base, state, batches, cfg_dyn, part):
        _, metrics = real(self, base, state, batches, cfg_dyn, part)
        return state, metrics

    monkeypatch.setattr(sfl.SflLLM, "_step_impl", step)


def _half_batch(monkeypatch):
    from repro.core import sfl

    real = sfl.SflLLM._step_impl

    def step(self, base, state, batches, cfg_dyn, part):
        lab = batches["labels"]
        K, b = lab.shape[:2]
        keep = (jnp.arange(K * b) < (K * b + 1) // 2).reshape(K, b, 1)
        batches = dict(batches, labels=jnp.where(keep, lab, -1))
        return real(self, base, state, batches, cfg_dyn, part)

    monkeypatch.setattr(sfl.SflLLM, "_step_impl", step)


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch])
def test_broken_timed_path_is_not_correct(root, capsys, monkeypatch, fault):
    fault(monkeypatch)
    out = bench_tiny.run_cell(root, bench_tiny.TRAIN, capsys, seconds=3.0)
    assert out["correct"] is False, out["checks"]


def test_control_fails_the_limits(root):
    """The reference computed in bfloat16 in the program's place, against
    the float32 reference at ``highest``: at least one number over its
    limit; the adapters' change is the one that shows it (their updates
    fall under half a bfloat16 step of the adapters' own size)."""
    import control

    cell = spec.load_cell(bench_tiny.TRAIN, root)
    got = control.train(cell, 11)
    assert got["program"]["passed"] is True, got
    assert got["control_bf16"]["passed"] is False, got
    assert got["control_bf16"]["update_gap"] > 10 * got["program"]["update_gap"]
    assert got["fault_half_batch"]["passed"] is False, got
