"""The reduction from trace to per-layer numbers, on hand-built events
and on a short trace recorded on a TPU v5e."""
import os

import pytest

from harness import trace as T

#: one training step of ``rwkv6-3b.train.orq9-ef`` traced on a TPU v5e
#: (``run.py --seconds 0.5 --trace 1``): the device ops and host spans that
#: ``Trace.from_file`` read from its ``.xplane.pb``, as gzipped JSON
TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "train_step.trace.json.gz")


def test_union_merges_overlaps_and_drops_empty():
    assert T.union([(5, 7), (0, 2), (1, 3), (4, 4), (7, 8)]) == [
        (0, 3), (5, 8)]
    assert T.total([(0, 2), (1, 3), (10, 11)]) == 4


def test_clip_and_gaps():
    busy = [(1, 2), (4, 6), (5, 9)]
    assert T.clip(busy, 0, 5) == [(1, 2), (4, 5)]
    assert T.gaps(busy, 0, 10) == [(0, 1), (2, 4), (9, 10)]
    assert T.gaps([], 3, 4) == [(3, 4)]


@pytest.mark.parametrize("coll,other,want", [
    ([(0, 10)], [], 10),                       # nothing overlaps
    ([(0, 10)], [(2, 4), (6, 8)], 6),          # two holes
    ([(0, 10)], [(-5, 15)], 0),                # fully hidden
    ([(0, 4), (3, 10)], [(2, 5), (4, 6)], 6),  # merged both sides
    ([(0, 2), (8, 10)], [(1, 9)], 2),          # other spans the gap
])
def test_exposed_collective_time(coll, other, want):
    assert T.exposed(coll, other) == want


def test_group_by_name():
    ev = [("a", 0, 2), ("b", 1, 3), ("a", 5, 1)]
    assert T.group_by_name(ev) == {"a": (2, 3), "b": (1, 3)}


def test_is_collective_names():
    for n in ("all-to-all.3", "all-gather-start.1", "all-reduce.12",
              "reduce-scatter", "collective-permute-done.2"):
        assert T.is_collective(n)
    for n in ("fusion.3", "custom-call.1", "copy-start"):
        assert not T.is_collective(n)


def test_label_gaps_by_host_span():
    idle = [(0, 10), (20, 21), (30, 60)]
    spans = [("tick", 0, 8), ("wait", 35, 30)]
    got = T.label_gaps(idle, spans, top=2)
    assert [n for n, _ in got] == ["wait", "tick"]
    assert [d for _, d in got] == pytest.approx([30e-9, 10e-9])


def _hand_trace():
    ops = {"/device:TPU:0": [("fusion.1", 0, 10), ("all-reduce.1", 5, 10),
                             ("custom-call.2", 30, 10)],
           "/device:TPU:1": [("fusion.1", 0, 20), ("all-reduce.1", 20, 10)]}
    spans = [("decode", 0, 25), ("prefill", 28, 20)]
    return T.Trace(ops, spans, window=(0, 50))


def test_trace_numbers_on_hand_events():
    t = _hand_trace()
    assert t.busy_ns("/device:TPU:0") == 25
    assert t.mean_busy_ns() == (25 + 30) / 2
    assert t.mean_exposed_collective_ns() == (5 + 10) / 2
    assert t.mean_op_ns(lambda n: n.startswith("fusion")) == 15
    (name, secs), = t.top_ops(1)
    assert name == "fusion.1" and secs == pytest.approx(15e-9)
    # ops of the first device that start inside the decode span
    assert t.span_op_ns("decode", lambda n: True) == (20, 1)
    (name, secs), = t.idle_gaps(1)
    assert name == "decode" and secs == pytest.approx(15e-9)


def test_trace_window_cuts_ops():
    t = T.Trace({"/device:TPU:0": [("a", 0, 10), ("b", 40, 20)]}, [],
                window=(5, 50))
    assert t.device_ops("/device:TPU:0") == [("a", 5, 5), ("b", 40, 10)]


def test_recorded_chip_trace():
    import gzip
    import json

    from harness.kernels import is_exchange_kernel

    with gzip.open(TRACE) as f:
        doc = json.load(f)
    t = T.Trace({d: [tuple(e) for e in evs] for d, evs in doc["ops"].items()},
                [tuple(e) for e in doc["spans"]], window=tuple(doc["window"]))
    assert [n for n, _, _ in t.spans] == ["step"]
    # one jitted step: the device is busy all but 0.2 % of the window
    assert 0.99 * t.window_ns < t.mean_busy_ns() <= t.window_ns
    names = {n for evs in t.ops.values() for n, _, _ in evs}
    assert {"encode_fused", "encode_fused.1", "qdq_fused.1",
            "decode_fused_mean.1", "decode_fused_each.1"} <= names
    # the exchange kernels: 60.37 ms of the 1.298 s step
    assert t.mean_op_ns(is_exchange_kernel) * 1e-6 == pytest.approx(60.37,
                                                                    abs=0.01)
    # the level fit's two bucket sorts are the longest ops outside the
    # while loops (a loop's event spans the ops nested in it)
    top = max((e for e in t.device_ops("/device:TPU:0")
               if not e[0].startswith("while")), key=lambda e: e[2])
    assert top[0].startswith("sort") and top[2] > 0.15 * t.window_ns
    assert not any(T.is_collective(n) for n in names)
    assert t.mean_exposed_collective_ns() == 0
