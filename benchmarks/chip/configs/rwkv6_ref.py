"""Plain float32 reference of RWKV-6 "Finch" training (arXiv:2404.05892).

Straight ``jax.numpy`` with no kernels, no chunked WKV, no batching
tricks, under ``default_matmul_precision("highest")``. It follows the
program's stated layer (which it does not import):

    x = embed[tokens]
    per layer:  h = x + time_mix(rms1(x));  x = h + channel_mix(rms2(h))
    logits = rms_f(x) @ head;  loss = mean next-token cross-entropy

time_mix: token shift ``dx = x[t-1] - x[t]``, the five-way data-dependent
lerp (``mu`` + a tanh LoRA) giving r, k, v, g and the decay input; decay
``w = exp(-exp(w0 + tanh(x_w A) B))``; the WKV recurrence, one token at a
time, per head:

    out_t = r_t . (S_{t-1} + diag(u) k_t^T v_t),  S_t = diag(w_t) S_{t-1} + k_t^T v_t

then a per-head group norm (eps 1e-5, scale ``ln_x``), times silu(g), and
the output projection. channel_mix: ``sigmoid(x_r W_r) * (relu(x_k W_k)^2
W_v)`` with its own token shift. Norms are RMSNorm with a (1 + scale)
weight, eps ``norm_eps``.

Departures from the published model, as the program has them: RMSNorm in
place of LayerNorm around the blocks and at the head; the head's
vocabulary is this configuration's slice.

``matmul="fp8"`` is the control, one precision step below the bf16
matmuls the configuration states, as fp8 training computes them: both
operands of every matmul rounded to float8_e4m3fn and the cotangent of its
output to float8_e5m2, each tensor scaled first so that its largest
magnitude lands on the format's largest value (so nothing underflows that
the format could hold).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

WKV_CHUNK = 64          # tokens per checkpointed block of the recurrence
LOSS_CHUNK = 512        # tokens per checkpointed block of the loss


def _scaled_round(x, dtype):
    """``x`` rounded to ``dtype`` with per-tensor scaling: its largest
    magnitude is scaled to the format's largest value first, and the
    result scaled back."""
    top = float(jnp.finfo(dtype).max)
    amax = jnp.max(jnp.abs(x))
    s = jnp.where(amax > 0, top / amax, 1.0)
    y = jnp.clip(x * s, -top, top).astype(dtype).astype(x.dtype)
    return y / s


@jax.custom_vjp
def _fp8_operand(x):
    """A matmul operand in float8_e4m3fn; the cotangent passes through."""
    return _scaled_round(x, jnp.float8_e4m3fn)


_fp8_operand.defvjp(lambda x: (_fp8_operand(x), None), lambda _, g: (g,))


@jax.custom_vjp
def _fp8_cotangent(y):
    """The identity, whose cotangent is rounded to float8_e5m2."""
    return y


_fp8_cotangent.defvjp(lambda y: (y, None),
                      lambda _, g: (_scaled_round(g, jnp.float8_e5m2),))


def _mm(a, b, matmul: str):
    if matmul == "fp8":
        return _fp8_cotangent(jnp.matmul(_fp8_operand(a), _fp8_operand(b)))
    return jnp.matmul(a, b)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + scale)


def _shift(x):
    return jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :-1]


def wkv(r, k, v, w, u):
    """The recurrence, token by token. r, k, v, w (B, S, H, hd) f32,
    u (H, hd) -> (B, S, H, hd)."""
    B, S, H, hd = r.shape
    T = WKV_CHUNK if S % WKV_CHUNK == 0 else S

    def step(s, xs):
        rt, kt, vt, wt = xs                               # (B, H, hd)
        kv = kt[..., :, None] * vt[..., None, :]          # (B, H, hd, hd)
        out = jnp.einsum("bhij,bhi->bhj", s + u[None, :, :, None] * kv, rt)
        return wt[..., :, None] * s + kv, out

    @jax.checkpoint
    def block(s, xs):
        return jax.lax.scan(step, s, xs)

    def tm(x):          # (B, S, H, hd) -> (S/T, T, B, H, hd)
        return x.transpose(1, 0, 2, 3).reshape(S // T, T, B, H, hd)

    s0 = jnp.zeros((B, H, hd, hd), jnp.float32)
    _, out = jax.lax.scan(block, s0, tuple(map(tm, (r, k, v, w))))
    return out.reshape(S, B, H, hd).transpose(1, 0, 2, 3)


def layer(p, x, c: dict, matmul: str):
    """One RWKV-6 layer on x (B, S, D) f32; ``p`` holds its leaves."""
    B, S, D = x.shape
    hd = c["sizes"]["rwkv"]["head_dim"]
    H = D // hd
    eps = c["sizes"]["norm_eps"]
    mm = partial(_mm, matmul=matmul)

    xn = _rms(x, p["norm1"]["scale"], eps)
    dx = _shift(xn) - xn
    xx = xn + dx * p["tm_mu"][0]
    a = jnp.tanh(mm(xx, p["tm_w1"])).reshape(B, S, 5, -1)
    mods = jnp.stack([mm(a[:, :, f], p["tm_w2"][f]) for f in range(5)])
    xr, xk, xv, xg, xw = (xn + dx * (p["tm_mu"][1 + f] + mods[f])
                          for f in range(5))
    r = mm(xr, p["wr"]).reshape(B, S, H, hd)
    k = mm(xk, p["wk"]).reshape(B, S, H, hd)
    v = mm(xv, p["wv"]).reshape(B, S, H, hd)
    g = jax.nn.silu(mm(xg, p["wg"]))
    dec = p["w0"] + mm(jnp.tanh(mm(xw, p["dec_w1"])), p["dec_w2"])
    w = jnp.exp(-jnp.exp(dec)).reshape(B, S, H, hd)
    o = wkv(r, k, v, w, p["u"])
    mu = o.mean(-1, keepdims=True)
    var = ((o - mu) ** 2).mean(-1, keepdims=True)
    o = ((o - mu) * jax.lax.rsqrt(var + 1e-5)).reshape(B, S, D) * p["ln_x"]
    h = x + mm(o * g, p["wo"])

    hn = _rms(h, p["norm2"]["scale"], eps)
    dh = _shift(hn) - hn
    kk = jnp.square(jax.nn.relu(mm(hn + dh * p["cm_mu_k"], p["ck"])))
    rr = jax.nn.sigmoid(mm(hn + dh * p["cm_mu_r"], p["cr"]))
    return h + rr * mm(kk, p["cv"])


def loss(params, tokens, c: dict, matmul: str = "f32",
         half_batch: bool = False):
    """Mean next-token cross-entropy of tokens (B, S + 1).
    ``half_batch`` takes the mean over the first half of the rows only,
    or of a single row's positions (a planted fault)."""
    if half_batch and tokens.shape[0] > 1:
        tokens = tokens[: tokens.shape[0] // 2]
    elif half_batch:
        tokens = tokens[:, : tokens.shape[1] // 2 + 1]
    L = c["sizes"]["num_layers"]
    eps = c["sizes"]["norm_eps"]
    inp, tgt = tokens[:, :-1], tokens[:, 1:]
    x = jnp.take(params["embed"], inp, axis=0)
    stack = params["groups"][0]["pos0"]
    body = jax.checkpoint(partial(layer, c=c, matmul=matmul))
    for i in range(L):
        x = body(jax.tree_util.tree_map(lambda a: a[i], stack), x)
    x = _rms(x, params["final_norm"], eps)
    B, S, D = x.shape
    n = S // LOSS_CHUNK if S % LOSS_CHUNK == 0 else 1

    @jax.checkpoint
    def chunk(tot, xs):
        xc, tc = xs
        lg = _mm(xc, params["lm_head"], matmul)
        nll = jax.nn.logsumexp(lg, -1) - jnp.take_along_axis(
            lg, tc[..., None], -1)[..., 0]
        return tot + nll.sum(), None

    xs = (x.reshape(B, n, S // n, D).swapaxes(0, 1),
          tgt.reshape(B, n, S // n).swapaxes(0, 1))
    tot, _ = jax.lax.scan(chunk, jnp.float32(0), xs)
    return tot / (B * S)


# ------------------------------------------------ the configuration's counts

def layer_params(c: dict) -> int:
    """Matmul parameters of one RWKV-6 layer: r, k, v, g, o and the
    channel-mix receptance (6 D^2), the channel-mix key/value (2 D F), the
    five-way token-shift LoRA (10 D lora_mix) and the decay LoRA
    (2 D lora_decay)."""
    D, F = c["d_model"], c["d_ff"]
    r = c["rwkv"]
    return 6 * D * D + 2 * D * F + 10 * D * r["lora_mix"] \
        + 2 * D * r["lora_decay"]


def train_flops_per_token(c: dict) -> float:
    """Forward and backward (3x forward) FLOPs per trained token: 2 per
    matmul parameter (layers and head; the embedding is a lookup) plus the
    WKV recurrence, 6 hd^2 per head per token (k^T v outer product, decay
    and add into the state, bonus term, read-out by r). Recompute is not
    counted."""
    D, V, L = c["d_model"], c["vocab_size"], c["num_layers"]
    hd = c["rwkv"]["head_dim"]
    H = D // hd
    fwd = 2 * (L * layer_params(c) + D * V) + L * 6 * H * hd * hd
    return 3.0 * fwd


def param_count(c: dict) -> int:
    """Every parameter: the layers' matmuls and their 13 D-sized vectors
    (two norms, six token-shift mixes, decay base, bonus, group-norm
    scale, two channel-mix mixes), the embedding, head and final norm."""
    D, V = c["d_model"], c["vocab_size"]
    return c["num_layers"] * (layer_params(c) + 13 * D) + 2 * V * D + D


def leaf_norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree_util.tree_leaves(tree)])


def train(params, batches, c: dict, lr: float, momentum: float,
          steps: int = 3, matmul: str = "f32", half_batch: bool = False):
    """``steps`` steps of SGD with momentum (m = momentum m + g, p -= lr m)
    from ``params`` (consumed) on ``batches``. Returns the loss of each
    step, each leaf's first-gradient norm and each leaf's change after the
    steps, as numpy arrays in the tree's leaf order."""
    with jax.default_matmul_precision("highest"):
        vg = jax.jit(jax.value_and_grad(
            partial(loss, c=c, matmul=matmul, half_batch=half_batch)))

        @partial(jax.jit, donate_argnums=(0, 1))
        def update(p, m, g):
            m = jax.tree_util.tree_map(lambda a, b: momentum * a + b, m, g)
            return jax.tree_util.tree_map(lambda a, b: a - lr * b, p, m), m

        p0 = jax.tree_util.tree_map(jnp.copy, params)
        m = jax.tree_util.tree_map(jnp.zeros_like, params)
        losses, gnorm = [], None
        for i in range(steps):
            val, g = vg(params, batches[i])
            losses.append(float(val))
            if i == 0:
                gnorm = np.asarray(jax.jit(leaf_norms)(g))
            params, m = update(params, m, g)
            del g
        delta = np.asarray(jax.jit(lambda a, b: leaf_norms(
            jax.tree_util.tree_map(jnp.subtract, a, b)))(params, p0))
    return {"losses": np.asarray(losses), "grad_norms": gnorm,
            "delta_norms": delta}
