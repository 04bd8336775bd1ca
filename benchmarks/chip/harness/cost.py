"""Operations and bytes that the algorithms require, from shapes alone.

These are the yardstick's own counts: they read a configuration file's
sizes, never the program, and count the work the algorithm needs whatever
implements it (no recompute, no padding, no implementation scratch).
"""
from __future__ import annotations

import json
import math
import os
from typing import Dict

PEAKS_FILE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "peaks.json")


def peaks(device_kind: str) -> Dict[str, float]:
    """The published peaks of ``device_kind``; an unknown kind raises."""
    with open(PEAKS_FILE) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"device_kind {device_kind!r} is not in {PEAKS_FILE}"
                       f" (known: {sorted(table)})")
    return table[device_kind]


# ------------------------------------------------------------ required bytes

def quantizer_shape(name: str) -> Dict[str, int]:
    """Levels per bucket and bits per element on the wire of a scheme:
    ``orq-<s>`` has s levels in ceil(log2 s) bits, ``terngrad`` 3 in 2,
    ``bingrad-b`` 2 in 1."""
    fixed = {"terngrad": 3, "bingrad-b": 2, "bingrad-pb": 2, "signsgd": 2}
    if name in fixed:
        levels = fixed[name]
    elif name.startswith("orq-"):
        levels = int(name[4:])
    else:
        raise KeyError(f"no wire shape for scheme {name!r}")
    return {"levels": levels, "bits": max(1, math.ceil(math.log2(levels)))}


def packed_words(d: int, bits: int) -> int:
    """uint32 words that hold ``d`` fields of ``bits`` bits, fields never
    straddling a word (floor(32 / bits) to a word)."""
    return -(-d // (32 // bits))


def wire_bytes(n: int, bucket: int, bits: int, levels: int) -> int:
    """Bytes on the wire for ``n`` elements: per bucket the packed indices
    and ``levels`` float32 levels."""
    nb = -(-n // bucket)
    return nb * 4 * (packed_words(bucket, bits) + levels)


def exchange_bytes(n: int, bucket: int, bits: int, levels: int,
                   workers: int, error_feedback: bool) -> Dict[str, int]:
    """HBM bytes one worker's kernels must move in one quantized
    Algorithm-2 all-reduce of ``n`` float32 elements over ``workers``:

    * encode: read the gradient, write its wire;
    * decode-mean: read the ``workers`` received wire shards (one wire's
      worth), write the float32 mean of this worker's 1/workers share;
    * requantize: read that mean, write its wire;
    * final decode: read the gathered wire, write the float32 result;
    * error feedback: read the gradient, write its local dequantized copy.
    """
    w = wire_bytes(n, bucket, bits, levels)
    share = -(-n // workers)
    w_share = wire_bytes(share, bucket, bits, levels)
    out = {"encode": 4 * n + w,
           "decode_mean": w + 4 * share,
           "requant": 4 * share + w_share,
           "decode": workers * w_share + 4 * n}
    if error_feedback:
        out["qdq"] = 8 * n
    return out


def roofline_share(bytes_: float, flops: float, seconds: float,
                   pk: Dict[str, float]) -> float:
    """Least time the chip could take (the larger of FLOPs over the bf16
    peak and bytes over the HBM peak) over ``seconds``, in %."""
    least = max(flops / pk["bf16_flops"], bytes_ / pk["hbm_bytes_per_s"])
    return 100.0 * least / seconds
