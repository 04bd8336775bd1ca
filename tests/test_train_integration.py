"""End-to-end training integration: single-device Algorithm 2 loop (loss
decreases under quantization) and multi-device fsdp/replicated equivalence
(subprocess with 8 fake devices)."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_smoke_config
from repro.core import QuantConfig
from repro.data import SyntheticLM
from repro.models import LM
from repro.optim.schedule import constant_lr
from repro.train import TrainConfig, make_train_step
from repro.train.step import init_state

jax.config.update("jax_platform_name", "cpu")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_devices(body, n=8):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(body)],
                         env=env, capture_output=True, text=True,
                         timeout=1200)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


def _train_single(quant_name: str, steps: int = 30):
    cfg = get_smoke_config("lm-100m")
    model = LM(cfg)
    mesh = jax.make_mesh((1,), ("data",))
    tcfg = TrainConfig(policy=QuantConfig(name=quant_name, bucket_size=512),
                       mode="replicated")
    state = init_state(model, mesh, tcfg, jax.random.key(0))
    step_fn, _ = make_train_step(model, mesh, tcfg, constant_lr(0.05))
    data = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=32, batch_size=8,
                       seed=3)
    losses = []
    for i in range(steps):
        state, metrics = step_fn(state, data.batch(i), jax.random.key(42))
        losses.append(float(metrics["loss"]))
    return losses


class TestSingleMachine:
    """Paper's single-machine mode: grads quantize->dequantize every step."""

    def test_fp_loss_decreases(self):
        losses = _train_single("fp")
        assert losses[-1] < losses[0] - 0.5, losses[::10]

    @pytest.mark.parametrize("name", ["orq-9", "bingrad-b", "terngrad"])
    def test_quantized_loss_decreases(self, name):
        losses = _train_single(name)
        assert np.isfinite(losses).all()
        assert losses[-1] < losses[0] - 0.3, (name, losses[::10])


def test_host_mesh_quantized_step_multi_device():
    """The launchers' mesh (``make_host_mesh``, data=4 x model=1): orq-9
    with error feedback trains in replicated and fsdp mode, replicas stay
    bit-identical, and step 0's loss equals fp's (same params, same
    batch). jax 0.9's ``make_mesh`` defaults to Explicit axes, under
    which the model's ``model``-axis sharding hints are refused."""
    out = run_devices("""
import hashlib
import jax, numpy as np
from repro.configs.base import get_smoke_config
from repro.core import QuantPolicy
from repro.data import SyntheticLM
from repro.launch.mesh import make_host_mesh
from repro.models import LM
from repro.optim.schedule import constant_lr
from repro.train import TrainConfig, make_train_step
from repro.train.step import init_state

cfg = get_smoke_config("lm-100m")
model = LM(cfg)
mesh = make_host_mesh(data=4, model=1)
data = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=16, batch_size=8,
                   seed=1)
for mode in ("replicated", "fsdp"):
    first = {}
    for quant in ("orq-9", "fp"):
        tcfg = TrainConfig(policy=QuantPolicy.parse(quant, bucket_size=512),
                           mode=mode, error_feedback=quant != "fp")
        state = init_state(model, mesh, tcfg, jax.random.key(0))
        step, _ = make_train_step(model, mesh, tcfg, constant_lr(0.05))
        losses = []
        for i in range(2):
            state, m = step(state, data.batch(i), jax.random.key(1))
            losses.append(float(m["loss"]))
        assert np.isfinite(losses).all(), (mode, quant, losses)
        first[quant] = losses[0]
        if mode == "replicated":
            digests = set()
            for d in range(4):
                h = hashlib.sha256()
                for leaf in jax.tree_util.tree_leaves(state.params):
                    h.update(np.asarray(leaf.addressable_shards[d].data)
                             .tobytes())
                digests.add(h.hexdigest())
            assert len(digests) == 1, (mode, quant)
    assert first["orq-9"] == first["fp"], (mode, first)
print("OK")
""", n=4)
    assert "OK" in out


def test_fsdp_mode_multi_device():
    """fsdp mode on a 4x2 (data, model) mesh: runs, loss decreases, and the
    fp-quantizer fsdp step matches the replicated fp step numerically."""
    run_devices("""
import jax, jax.numpy as jnp, numpy as np
from repro.configs.base import get_smoke_config
from repro.core import QuantConfig
from repro.data import SyntheticLM
from repro.models import LM
from repro.optim.schedule import constant_lr
from repro.train import TrainConfig, make_train_step
from repro.train.step import init_state

cfg = get_smoke_config("lm-100m")
model = LM(cfg)
mesh = jax.make_mesh((4, 2), ("data", "model"))
data = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=32, batch_size=8,
                   seed=3)

def run(mode, quant):
    tcfg = TrainConfig(policy=QuantConfig(name=quant, bucket_size=512),
                       mode=mode)
    state = init_state(model, mesh, tcfg, jax.random.key(0))
    step_fn, plan = make_train_step(model, mesh, tcfg, constant_lr(0.05))
    losses = []
    for i in range(8):
        state, m = step_fn(state, data.batch(i), jax.random.key(42))
        losses.append(float(m["loss"]))
    return losses, state

l_fsdp_fp, s1 = run("fsdp", "fp")
l_repl_fp, s2 = run("replicated", "fp")
print("fsdp fp:", l_fsdp_fp)
print("repl fp:", l_repl_fp)
# same math up to bf16 gather noise and reduction order
np.testing.assert_allclose(l_fsdp_fp, l_repl_fp, rtol=0.05)
assert l_fsdp_fp[-1] < l_fsdp_fp[0]

l_q, _ = run("fsdp", "orq-5")
print("fsdp orq-5:", l_q)
assert np.isfinite(l_q).all()
assert l_q[-1] < l_q[0]
print("OK")
""")


def test_whisper_train_multi_device():
    """Enc-dec arch trains under fsdp mode (exercises encoder gathers)."""
    run_devices("""
import jax, jax.numpy as jnp, numpy as np
from repro.configs.base import get_smoke_config
from repro.core import QuantConfig
from repro.models import LM
from repro.optim.schedule import constant_lr
from repro.train import TrainConfig, make_train_step
from repro.train.step import init_state

cfg = get_smoke_config("whisper-base")
model = LM(cfg)
mesh = jax.make_mesh((4, 2), ("data", "model"))
tcfg = TrainConfig(policy=QuantConfig(name="orq-5", bucket_size=256),
                   mode="fsdp")
state = init_state(model, mesh, tcfg, jax.random.key(0))
step_fn, _ = make_train_step(model, mesh, tcfg, constant_lr(0.05))
key = jax.random.key(1)
batch = {
    "tokens": jax.random.randint(key, (8, 16), 0, cfg.vocab_size),
    "enc_embeds": jax.random.normal(key, (8, cfg.encoder.num_frames,
                                          cfg.d_model)) * 0.02,
}
for i in range(3):
    state, m = step_fn(state, batch, jax.random.key(2))
    assert np.isfinite(float(m["loss"])), m
print("whisper OK", float(m["loss"]))
""")


def test_moe_arch_multi_device():
    """MoE + hybrid archs train under fsdp with quantized comm."""
    run_devices("""
import jax, numpy as np
from repro.configs.base import get_smoke_config
from repro.core import QuantConfig
from repro.models import LM
from repro.optim.schedule import constant_lr
from repro.train import TrainConfig, make_train_step
from repro.train.step import init_state

mesh = jax.make_mesh((4, 2), ("data", "model"))
for arch in ["mixtral-8x22b", "jamba-v0.1-52b", "rwkv6-3b"]:
    cfg = get_smoke_config(arch)
    model = LM(cfg)
    tcfg = TrainConfig(policy=QuantConfig(name="terngrad", bucket_size=256),
                       mode="fsdp")
    state = init_state(model, mesh, tcfg, jax.random.key(0))
    step_fn, _ = make_train_step(model, mesh, tcfg, constant_lr(0.02))
    batch = {"tokens": jax.random.randint(jax.random.key(1), (8, 16), 0,
                                          cfg.vocab_size)}
    state, m = step_fn(state, batch, jax.random.key(2))
    assert np.isfinite(float(m["loss"])), arch
    print(arch, "OK", float(m["loss"]))
""")
