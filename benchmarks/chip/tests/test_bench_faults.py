"""A run with its timed path broken underneath comes out not correct.

Each test skips the harness's look for a chip and drives the rest of a
training run at a CPU size, once as it is and once for each fault that a
one-chip training cell can have: a step that returns its state unchanged,
and one that takes the mean over half the batch. The limits are those of
the cell's comparison set for this size (``SMALL_LIMITS``): the sound run
comes out correct under them and each broken one does not. The control
test beside them runs the control (the reference with fp8 matmuls in the
program's place) at the same size."""
import io
import json
from contextlib import redirect_stdout
from types import SimpleNamespace

import pytest

from harness import common, cost, train


#: the cell's compared numbers with limits for this size, set from its
#: readings over six seeds: the sound program's largest loss gap 0.0016 -
#: 0.016 (half the batch: 0.13 - 0.16), its median leaf's first-gradient
#: and change gaps up to 0.048 and 0.072 (a state left unchanged: 1)
SMALL_LIMITS = {"loss_gap": 0.05, "median_grad_norm_gap": 0.5,
                "median_update_norm_gap": 0.3}


def _result(run, cell, monkeypatch):
    import jax

    monkeypatch.setattr(cost, "peaks", lambda kind: {
        "bf16_flops": 197e12, "hbm_bytes_per_s": 819e9})
    monkeypatch.setattr(common, "load_limits",
                        lambda name: dict(SMALL_LIMITS))
    buf = io.StringIO()
    with redirect_stdout(buf):
        run(cell, SimpleNamespace(seed=2**32 + 11, seconds=1.0, trace=0),
            jax.devices()[:1], common.CompileCounter(), 0.0)
    return json.loads(buf.getvalue().strip().splitlines()[-1])


def _train_cell(config, traffic):
    return common.Cell(name="small", chips=1, config=config, traffic=traffic,
                       end_to_end=[], per_layer=[])


def _break_step(monkeypatch, how):
    import jax

    import repro.train as rt

    real = rt.make_train_step

    def broken(model, mesh, tcfg, lr_fn=None, **kw):
        step, plan = real(model, mesh, tcfg, lr_fn, **kw)

        def fn(state, batch, key):
            if how == "unchanged":
                return state, step(state, batch, key)[1]
            half = batch["tokens"].shape[0] // 2
            return step(state, {"tokens": batch["tokens"][:half]}, key)

        return jax.jit(fn), plan

    monkeypatch.setattr(rt, "make_train_step", broken)


def test_sound_train_step_is_correct(rwkv_small, train_traffic_small,
                                     monkeypatch):
    res = _result(train.run, _train_cell(rwkv_small, train_traffic_small),
                  monkeypatch)
    assert res["correct"] is True, res["checks"]
    assert set(res["checks"]) == set(SMALL_LIMITS) | {"window_compiles"}
    assert set(SMALL_LIMITS) == set(common.load_json(
        f"{common.BENCH_DIR}/limits/rwkv6-3b.train.orq9-ef.json"))


@pytest.mark.parametrize("how", ["unchanged", "half_batch"])
def test_broken_train_step_is_not_correct(how, rwkv_small,
                                          train_traffic_small, monkeypatch):
    _break_step(monkeypatch, how)
    res = _result(train.run, _train_cell(rwkv_small, train_traffic_small),
                  monkeypatch)
    assert res["correct"] is False, res["checks"]


def test_control_reads_beyond_the_program(rwkv_small, train_traffic_small):
    """At this size the control's largest loss gap reads 0.011 - 0.037 and
    the sound program's 0.0016 - 0.016 over five seeds: above it on four.
    At the cell's size no number separates them (PERF.md)."""
    import jax

    tc = train.TrainCell(_train_cell(rwkv_small, train_traffic_small),
                         jax.devices()[:1])
    above = []
    for seed in (2**31 + 5, 3, 2**40 + 7):
        prog = tc.start(seed)
        del prog["state"], prog["batches"]
        ref = tc.reference(seed)
        low = train.numbers(tc.reference(seed, matmul="fp8"), ref)
        sound = train.numbers(prog, ref)
        above.append(low["loss_gap"] > sound["loss_gap"])
    assert sum(above) >= 2, above
