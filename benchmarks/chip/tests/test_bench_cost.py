"""The benchmark's FLOPs, required bytes and peaks against hand counts."""
import json
import os

import pytest

from harness import common, cost

CONFIGS = os.path.join(common.BENCH_DIR, "configs")


def _config(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


def _ref(name):
    return common.reference_module(_config(name))


def test_rwkv6_flops_per_token_hand_count():
    c = _config("rwkv6-3b.stage4")["sizes"]
    ref = _ref("rwkv6-3b.stage4")
    D, F, L, V = 2560, 8960, 4, 16384
    mats = ([D * D] * 6                    # r, k, v, g, o, channel-mix r
            + [D * F, F * D]                # channel-mix k, v
            + [D * 5 * 32, 5 * 32 * D]      # token-shift LoRA
            + [D * 64, 64 * D])             # decay LoRA
    assert sum(mats) == 86_343_680 == ref.layer_params(c)
    wkv = 6 * 40 * 64 * 64                  # per token and layer
    fwd = 2 * (L * sum(mats) + D * V) + L * wkv
    assert fwd == 778_567_680
    assert ref.train_flops_per_token(c) == 3 * fwd == 2_335_703_040


@pytest.mark.parametrize("name,want", [("rwkv6-3b.stage4", 429_396_480)])
def test_param_count_matches_the_programs_tree(name, want):
    import jax

    from repro.models import LM

    conf = _config(name)
    model = LM(common.model_config(conf))
    tree = jax.eval_shape(model.init, jax.random.key(0))
    n = sum(x.size for x in jax.tree_util.tree_leaves(tree))
    assert n == _ref(name).param_count(conf["sizes"]) == want


def test_exchange_bytes_small_shape():
    # 10,000 float32 elements, 2048-element buckets, orq-9 (9 levels in 4
    # bits, 8 fields a word: 256 words a bucket), over 4 workers
    got = cost.exchange_bytes(10_000, 2048, 4, 9, 4, True)
    wire = 5 * 4 * (256 + 9)            # 5 buckets
    wire_share = 2 * 4 * (256 + 9)      # 2,500 elements: 2 buckets
    assert got == {"encode": 40_000 + wire,
                   "decode_mean": wire + 10_000,
                   "requant": 10_000 + wire_share,
                   "decode": 4 * wire_share + 40_000,
                   "qdq": 80_000}
    assert "qdq" not in cost.exchange_bytes(10_000, 2048, 4, 9, 4, False)


@pytest.mark.parametrize("name,levels,bits", [
    ("orq-9", 9, 4), ("orq-5", 5, 3), ("orq-17", 17, 5), ("terngrad", 3, 2),
    ("bingrad-b", 2, 1)])
def test_quantizer_shape_matches_the_program(name, levels, bits):
    from repro.core import make_quantizer

    assert cost.quantizer_shape(name) == {"levels": levels, "bits": bits}
    qz = make_quantizer(name, bucket_size=2048)
    assert (qz.s, qz.wire_bits_per_element) == (levels, bits)
    assert cost.packed_words(2048, bits) * 32 // bits >= 2048


def test_peaks_v5e_and_unknown_kind():
    pk = cost.peaks("TPU v5 lite")
    assert pk["bf16_flops"] == 197e12 and pk["hbm_bytes_per_s"] == 819e9
    assert pk["ici_bytes_per_s"] * 8 == 1600e9
    with pytest.raises(KeyError, match="not in"):
        cost.peaks("TPU v9 imaginary")


def test_roofline_share_takes_the_larger_bound():
    pk = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert cost.roofline_share(10.0, 50.0, 2.0, pk) == 50.0   # bytes bound
    assert cost.roofline_share(1.0, 400.0, 8.0, pk) == 50.0   # FLOPs bound
