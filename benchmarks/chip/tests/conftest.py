"""Puts the benchmark's directory and the program's ``src`` on the path,
and gives the tests small versions of the benchmark's cells."""
import copy
import json
import os
import sys

import pytest

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(BENCH_DIR))
for p in (os.path.join(ROOT, "src"), BENCH_DIR):
    if p not in sys.path:
        sys.path.insert(0, p)

#: the RWKV-6 configuration at CPU size: every width cut, the structure
#: (period, heads, LoRAs) kept
RWKV_SMALL = dict(num_layers=2, d_model=128, d_ff=256, vocab_size=512,
                  rwkv={"head_dim": 32, "lora_mix": 16, "lora_decay": 16,
                        "chunk": 32})


def _load(*parts):
    with open(os.path.join(BENCH_DIR, *parts)) as f:
        return json.load(f)


@pytest.fixture
def rwkv_small():
    c = _load("configs", "rwkv6-3b.stage4.json")
    c["sizes"].update(copy.deepcopy(RWKV_SMALL))
    return c


@pytest.fixture
def train_traffic_small():
    t = _load("traffic", "train.orq9-ef.json")
    t.update(seq=64, batch_per_chip=2, bucket=256, batches=4)
    return t
