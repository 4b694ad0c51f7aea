"""Continuous-batching engine: outputs must equal independent greedy
generation per request, under mixed admission order and slot reuse;
outputs must be a pure function of the request (arrival order / occupancy independent);
prefill compiles must stay within the power-of-two bucket bound;
``bucket_len`` must stay a power of two (and >= the prompt) for
non-power-of-two ``max_len``.  The default engine here is the PAGED one
(auto-gated), so every end-to-end test doubles as paged coverage;
``tests/test_paged_kv.py`` holds the paged-specific properties."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro import models as M
from repro.models.generate import SampleConfig, generate
from repro.serving import Request, ServingEngine, bucket_len


def test_engine_matches_independent_generation(key):
    cfg = get_arch("gpt2-s").reduced(num_layers=2)
    params = M.init_params(cfg, key)
    rt = M.Runtime(attn_impl="naive")

    rng = np.random.default_rng(0)
    prompts = [rng.integers(5, cfg.vocab_size, rng.integers(4, 10)).tolist()
               for _ in range(6)]
    lens = [3, 5, 4, 6, 3, 4]

    eng = ServingEngine(cfg, params, rt=rt, max_slots=2, max_len=32,
                        sc=SampleConfig(greedy=True))
    reqs = [Request(uid=i, prompt=p, max_new_tokens=n)
            for i, (p, n) in enumerate(zip(prompts, lens))]
    for r in reqs:
        eng.submit(r)
    eng.run()
    assert all(r.done for r in reqs)

    for r, p, n in zip(reqs, prompts, lens):
        out, _ = generate(cfg, params, jnp.asarray(p, jnp.int32)[None],
                          rt=rt, max_new_tokens=n,
                          sc=SampleConfig(greedy=True))
        np.testing.assert_array_equal(np.asarray(r.output),
                                      np.asarray(out[0]), err_msg=f"req {r.uid}")


def test_engine_eos_frees_slot(key):
    cfg = get_arch("gpt2-s").reduced(num_layers=2)
    params = M.init_params(cfg, key)
    rt = M.Runtime(attn_impl="naive")
    # find the greedy first token for a prompt, use it as EOS
    prompt = [7, 8, 9, 10]
    out, _ = generate(cfg, params, jnp.asarray(prompt)[None], rt=rt,
                      max_new_tokens=1, sc=SampleConfig(greedy=True))
    eos = int(out[0, 0])
    eng = ServingEngine(cfg, params, rt=rt, max_slots=1, max_len=32)
    r1 = Request(uid=0, prompt=prompt, max_new_tokens=8, eos_id=eos)
    r2 = Request(uid=1, prompt=[11, 12, 13], max_new_tokens=2)
    eng.submit(r1)
    eng.submit(r2)
    eng.run()
    assert r1.done and len(r1.output) == 1       # stopped at EOS immediately
    assert r2.done and len(r2.output) == 2       # slot was reused


# ---------------------------------------------------------------------------
# outputs are a pure function of the request
# ---------------------------------------------------------------------------

def _shared_setup():
    cfg = get_arch("gpt2-s").reduced(num_layers=2)
    params = M.init_params(cfg, jax.random.key(0))
    rng = np.random.default_rng(1)
    prompts = [rng.integers(5, cfg.vocab_size, rng.integers(4, 12)).tolist()
               for _ in range(6)]
    return cfg, params, prompts


def _serve(cfg, params, prompts, order, *, sc, seed=7):
    eng = ServingEngine(cfg, params, rt=M.Runtime(attn_impl="naive"),
                        max_slots=2, max_len=32, sc=sc, seed=seed)
    reqs = {i: Request(uid=i, prompt=prompts[i], max_new_tokens=3 + i % 4)
            for i in order}
    for i in order:
        eng.submit(reqs[i])
    eng.run()
    assert all(r.done for r in reqs.values())
    return {i: r.output for i, r in reqs.items()}


@pytest.mark.parametrize("sc", [SampleConfig(greedy=True),
                                SampleConfig(temperature=0.7)],
                         ids=["greedy", "temperature"])
def test_outputs_independent_of_arrival_order(sc):
    """Regression for the seed engine's RNG draw-for-dead-slots bug: the
    same requests submitted in a different order (hence different slot
    occupancy patterns) must produce identical per-request outputs."""
    cfg, params, prompts = _shared_setup()
    a = _serve(cfg, params, prompts, [0, 1, 2, 3, 4, 5], sc=sc)
    b = _serve(cfg, params, prompts, [5, 2, 0, 4, 1, 3], sc=sc)
    assert a == b


def test_prefill_compiles_bounded_by_buckets():
    """Mixed prompt lengths must compile at most log2(max_len) prefill
    variants (power-of-two buckets), not one per distinct length."""
    cfg, params, _ = _shared_setup()
    max_len = 64
    eng = ServingEngine(cfg, params, rt=M.Runtime(attn_impl="naive"),
                        max_slots=2, max_len=max_len)
    rng = np.random.default_rng(3)
    lengths = sorted(set(rng.integers(3, 40, 12).tolist()))
    for i, n in enumerate(lengths):
        eng.submit(Request(uid=i, prompt=rng.integers(5, 50, n).tolist(),
                           max_new_tokens=2))
    eng.run()
    assert len(lengths) > math.log2(max_len)     # the bound is non-trivial
    assert eng.prefill_compiles() <= math.log2(max_len)


def test_bucketed_prefill_matches_exact_prefill():
    """Bucket padding is attention-masked: a padded prefill must yield the
    same generation as the exact-length one."""
    cfg, params, prompts = _shared_setup()
    sc = SampleConfig(greedy=True)
    rt = M.Runtime(attn_impl="naive")
    for buckets in (True, False):
        eng = ServingEngine(cfg, params, rt=rt, max_slots=1, max_len=32,
                            sc=sc, prefill_buckets=buckets)
        req = Request(uid=0, prompt=prompts[0], max_new_tokens=5)
        eng.submit(req)
        eng.run()
        ref, _ = generate(cfg, params, jnp.asarray(prompts[0])[None], rt=rt,
                          max_new_tokens=5, sc=sc)
        np.testing.assert_array_equal(np.asarray(req.output),
                                      np.asarray(ref[0]))


def test_bucket_len_non_power_of_two_max_len():
    """Regression: for non-power-of-two max_len the cap must round DOWN
    to a power of two — the old ``min(b, max_len)`` leaked max_len itself
    as a "bucket" (unbounded compile variants) and could return a bucket
    SHORTER than the prompt."""
    assert bucket_len(5, 48) == 8
    assert bucket_len(20, 48) == 32          # not 48
    assert bucket_len(32, 48) == 32
    assert bucket_len(3, 64) == 8            # floor unchanged
    assert bucket_len(33, 64) == 64          # power-of-two cap unchanged
    for n in (33, 40, 47):                   # gap prompts: cap < n < max_len
        with pytest.raises(AssertionError):
            bucket_len(n, 48)


def test_gap_length_prompts_served_exactly():
    """Prompts longer than the largest power-of-two bucket but shorter
    than a non-power-of-two max_len must be served (exact-length prefill),
    on both the bucketed and unbucketed slab paths."""
    cfg, params, _ = _shared_setup()
    rng = np.random.default_rng(9)
    prompt = rng.integers(5, cfg.vocab_size, 40).tolist()     # 32 < 40 < 48
    sc = SampleConfig(greedy=True)
    rt = M.Runtime(attn_impl="naive")
    ref, _ = generate(cfg, params, jnp.asarray(prompt)[None], rt=rt,
                      max_new_tokens=4, sc=sc)
    for buckets in (True, False):
        eng = ServingEngine(cfg, params, rt=rt, max_slots=1, max_len=48,
                            sc=sc, paged=False, prefill_buckets=buckets)
        req = Request(uid=0, prompt=prompt, max_new_tokens=4)
        eng.submit(req)
        eng.run()
        assert req.done
        np.testing.assert_array_equal(np.asarray(req.output),
                                      np.asarray(ref[0]))


@pytest.mark.parametrize("sc", [SampleConfig(greedy=True),
                                SampleConfig(temperature=0.7)],
                         ids=["greedy", "temperature"])
def test_paged_outputs_independent_of_page_layout(sc):
    """Satellite regression: the fold_in RNG contract must survive paging.
    The same request served on a FRESH pool vs after page-fragmenting
    churn (different physical pages, different slot, different free-list
    order) must produce identical tokens — same uid + token_idx => same
    draw, regardless of page layout."""
    cfg, params, prompts = _shared_setup()
    rt = M.Runtime(attn_impl="naive")
    probe = Request(uid=99, prompt=prompts[0], max_new_tokens=6)

    fresh = ServingEngine(cfg, params, rt=rt, max_slots=2, max_len=32,
                          sc=sc, seed=7, page_size=8)
    assert fresh.paged
    fresh.submit(Request(uid=99, prompt=prompts[0], max_new_tokens=6))
    r_fresh = fresh.queue[0]
    fresh.run()

    churned = ServingEngine(cfg, params, rt=rt, max_slots=2, max_len=32,
                            sc=sc, seed=7, page_size=8)
    # fragment the pool: interleaved lifetimes scramble the free list
    for i, n in enumerate((3, 9, 2, 7, 4)):
        churned.submit(Request(uid=i, prompt=prompts[i % len(prompts)],
                               max_new_tokens=n))
    churned.run()
    assert churned.pages_in_use() == 0
    churned.submit(probe)
    churned.run()
    assert probe.done and r_fresh.done
    assert probe.output == r_fresh.output


def test_fused_engine_with_flash_decode_runtime(key):
    """The serving default runtime (flash decode dispatch + fused dense)
    must agree with the plain naive runtime end to end."""
    cfg, params, prompts = _shared_setup()
    sc = SampleConfig(greedy=True)
    out = {}
    for name, rt in (("naive", M.Runtime(attn_impl="naive")),
                     ("serve", M.default_serve_runtime())):
        eng = ServingEngine(cfg, params, rt=rt, max_slots=2, max_len=32,
                            sc=sc)
        reqs = [Request(uid=i, prompt=p, max_new_tokens=4)
                for i, p in enumerate(prompts[:4])]
        for r in reqs:
            eng.submit(r)
        eng.run()
        out[name] = [r.output for r in reqs]
    assert out["naive"] == out["serve"]
