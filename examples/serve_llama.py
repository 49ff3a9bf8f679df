"""Continuous-batching LLM serving on the paged KV cache.

Demonstrates paddle_tpu.inference.ContinuousBatchingEngine: requests are
admitted whenever a batch lane and KV blocks are free, every decode tick
serves the whole active batch through ONE compiled step, finished
sequences retire and their blocks recycle mid-flight — the
iteration-level scheduling loop of modern LLM servers, built on a
block-paged KV pool so fragmentation never strands HBM.

Run: python examples/serve_llama.py              # one virtual CPU device
     python examples/serve_llama.py --devices 0  # the attached devices (a
                                                 # chip, where there is one)
"""

import argparse
import time

import numpy as np

from _common import setup_devices

parser = argparse.ArgumentParser()
parser.add_argument("--devices", default=1, type=int,
                    help="virtual CPU devices (0 = use attached hardware)")
args = parser.parse_args()
setup_devices(args.devices)

import paddle_tpu as paddle  # noqa: E402
from paddle_tpu.inference import ContinuousBatchingEngine  # noqa: E402
from paddle_tpu.models.llama import (  # noqa: E402
    LlamaConfig, LlamaForCausalLM)

paddle.seed(0)
cfg = LlamaConfig(vocab_size=512, hidden_size=128, intermediate_size=256,
                  num_hidden_layers=2, num_attention_heads=4,
                  max_position_embeddings=512)
model = LlamaForCausalLM(cfg)

engine = ContinuousBatchingEngine(model, num_blocks=96, block_size=8,
                                  max_batch=4, max_blocks_per_seq=24,
                                  prefill_buckets=(16, 32))

rng = np.random.RandomState(7)
requests = []
for i in range(10):   # oversubscribed 10 requests onto 4 lanes
    prompt = rng.randint(0, cfg.vocab_size, (rng.randint(4, 24),))
    rid = engine.add_request(prompt, max_new_tokens=int(rng.randint(4, 16)))
    requests.append((rid, prompt))

t0 = time.time()
results = engine.run()
dt = time.time() - t0

total = sum(len(v) for v in results.values())
print(f"served {len(requests)} requests / {total} tokens "
      f"in {dt:.2f}s on {paddle.device.get_device()}")
for rid, prompt in requests[:3]:
    print(f"  req {rid}: prompt[{len(prompt)}] -> {results[rid]}")
print(f"  ... ({len(requests) - 3} more)")
