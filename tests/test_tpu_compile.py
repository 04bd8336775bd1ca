"""Compile every fused Pallas kernel of the main path for a TPU v5e.

The TPU compiler is installed without the chip, so these tests compile
for a DESCRIBED v5e chip (``v5e:2x2`` topology, one device of it) at
lm-100m widths and assert that the kernel survives as a Mosaic
``tpu_custom_call``. They catch what interpret mode cannot: lane-splitting
reshapes, casts the chip has no instruction for, and VMEM overruns.

The topology is described only inside the module fixture: describing it
loads the TPU library, which one process at a time may hold, so it must
not happen while any module is imported. Where it cannot be described
the fixture skips. The persistent compile cache is off around these
compiles (a described-chip entry cannot be read back without the chip).
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import get_config
from repro.kernels import fused_bingrad, fused_decode, fused_encode, fused_kv

CFG = get_config("lm-100m")
NB, D = 4096, 2048                       # gradient buckets x bucket width
KV_HEADS, HD = CFG.num_kv_heads, CFG.resolved_head_dim
KV_D = KV_HEADS * HD                     # one KV bucket per token
KV_BATCH, KV_CONTEXT = 8, 1024
#: wire bits -> level count of the scheme that uses it
#: (minmax2, terngrad, orq-5, orq-9, orq-17)
LEVELS = {1: 2, 2: 3, 3: 5, 4: 9, 5: 17}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means: no TPU compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def spec(topo):
    """``spec(shape, dtype)``: an argument placed on one described chip."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


def _assert_mosaic(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("bits", sorted(LEVELS))
def test_encode_rr(spec, bits):
    s = LEVELS[bits]
    _assert_mosaic(
        lambda v, lv, r, m: fused_encode.encode_fused(
            v, lv, r, m, bits=bits, s=s, mode="rr", interpret=False),
        spec((NB, D), jnp.float32), spec((NB, s), jnp.float32),
        spec((NB, D), jnp.uint32), spec((NB, D), jnp.bool_))


@pytest.mark.parametrize("d", [768, 1000])
def test_encode_rr_narrow_bucket(spec, d):
    """Groups smaller than a bucket (norms, biases) ship d_eff < 2048,
    including widths whose words fill no whole lane tile."""
    _assert_mosaic(
        lambda v, lv, r, m: fused_encode.encode_fused(
            v, lv, r, m, bits=4, s=9, mode="rr", interpret=False),
        spec((NB, d), jnp.float32), spec((NB, 9), jnp.float32),
        spec((NB, d), jnp.uint32), spec((NB, d), jnp.bool_))


@pytest.mark.parametrize("mode", ["bin", "sign"])
def test_encode_deterministic(spec, mode):
    _assert_mosaic(
        lambda v, lv, m: fused_encode.encode_fused(
            v, lv, None, m, bits=1, s=2, mode=mode, interpret=False),
        spec((NB, D), jnp.float32), spec((NB, 2), jnp.float32),
        spec((NB, D), jnp.bool_))


@pytest.mark.parametrize("mode", ["rr", "bin"])
def test_qdq(spec, mode):
    s = 9 if mode == "rr" else 2
    args = [spec((NB, D), jnp.float32), spec((NB, s), jnp.float32)]
    if mode == "rr":
        args.append(spec((NB, D), jnp.uint32))
    args.append(spec((NB, D), jnp.bool_))

    def fn(v, lv, *rest):
        r, m = rest if mode == "rr" else (None, rest[0])
        return fused_encode.qdq_fused(v, lv, r, m, s=s, mode=mode,
                                      interpret=False)

    _assert_mosaic(fn, *args)


def test_encode_bingrad(spec):
    _assert_mosaic(
        lambda v, m: fused_bingrad.encode_bingrad_fused(v, m,
                                                        interpret=False),
        spec((NB, D), jnp.float32), spec((NB, D), jnp.bool_))


@pytest.mark.parametrize("bits", sorted(LEVELS))
@pytest.mark.parametrize("average", [True, False])
def test_decode(spec, bits, average):
    s, L = LEVELS[bits], 4
    nw = -(-D // (32 // bits))
    fn = (fused_decode.decode_fused_mean if average
          else fused_decode.decode_fused_each)
    _assert_mosaic(
        lambda w, lv: fn(w, lv, d=D, bits=bits, s=s, interpret=False),
        spec((L, NB // L, nw), jnp.uint32),
        spec((L, NB // L, s), jnp.float32))


@pytest.mark.parametrize("name", ["orq-9", "bingrad-b"])
def test_append_kv(spec, name, monkeypatch):
    """``append_kv`` encodes through ``wire.encode``, which picks interpret
    mode from the backend; the test steers it to the compiled kernel."""
    from repro.serve.kv_cache import KVQuantSpec

    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    qz = KVQuantSpec(name, KV_HEADS, HD).quantizer()
    rows = KV_BATCH * 16                 # one 16-token prefill chunk each
    args = [spec((rows, KV_D), jnp.float32)] * 2
    if name == "orq-9":
        args.append(spec((2 * rows, KV_D), jnp.uint32))
        fn = lambda k, v, r: fused_kv.append_kv(qz, k, v, r)  # noqa: E731
    else:
        fn = lambda k, v: fused_kv.append_kv(qz, k, v, None)  # noqa: E731
    _assert_mosaic(fn, *args)


@pytest.mark.parametrize("bits,T", [(4, 1), (1, 1), (4, 16), (5, 16)])
def test_decode_attend(spec, bits, T):
    s = LEVELS[bits]
    nw = -(-KV_D // (32 // bits))
    _assert_mosaic(
        lambda q, kw, klv, vw, vlv, m: fused_kv.decode_attend(
            q, kw, klv, vw, vlv, m, bits=bits, kv_heads=KV_HEADS,
            scale=HD ** -0.5, interpret=False),
        spec((KV_BATCH, T, CFG.num_heads, HD), jnp.float32),
        spec((KV_BATCH, KV_CONTEXT, nw), jnp.uint32),
        spec((KV_BATCH, KV_CONTEXT, s), jnp.float32),
        spec((KV_BATCH, KV_CONTEXT, nw), jnp.uint32),
        spec((KV_BATCH, KV_CONTEXT, s), jnp.float32),
        spec((KV_BATCH, T, KV_CONTEXT), jnp.bool_))
