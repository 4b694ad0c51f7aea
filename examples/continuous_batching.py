"""Continuous-batching serving demo: stream E2E-style requests of varying
length through a fixed-slot engine (deliverable b, serving scenario).

The engine decodes every live slot, samples, and advances slot state in
ONE jitted buffer-donated call per token; admission streams each prompt
through one compiled chunked-prefill program, whatever its length.

    PYTHONPATH=src python examples/continuous_batching.py
"""
import time

import jax

from repro.configs import get_arch
from repro.data import WordTokenizer, e2e_splits
from repro.data.tokenizer import SEP
from repro import models as M
from repro.models.generate import SampleConfig
from repro.serving import Request, ServingEngine

cfg = get_arch("gpt2-s").reduced(num_layers=4)
key = jax.random.key(0)
params = M.init_params(cfg, key)
lora = M.init_lora_stack(cfg, key, rank=4)

train, _, test = e2e_splits(500, 50, 50)
tok = WordTokenizer.from_corpus([e.text for e in train])


def make_requests():
    return [Request(uid=i, prompt=tok.encode(e.mr) + [SEP],
                    max_new_tokens=6 + i % 10)
            for i, e in enumerate(test[:10])]


eng = ServingEngine(cfg, params, lora=lora, max_slots=3, max_len=96,
                    sc=SampleConfig(greedy=True))
requests = make_requests()
for r in requests:
    eng.submit(r)
t0 = time.time()
steps = 0
while any(not r.done for r in requests):
    n = eng.step()
    steps += 1
    if steps % 5 == 0:
        done = sum(r.done for r in requests)
        print(f"step {steps:3d}: {n} live slots, "
              f"{done}/{len(requests)} done")
wall = time.time() - t0
total = sum(len(r.output) for r in requests)
print(f"\nserved {len(requests)} requests / {total} tokens in {steps} "
      f"engine steps ({wall:.1f}s), {eng.prefill_compiles()} prefill "
      f"compiles")
print("sample:", tok.decode(requests[0].output[:10]))
