"""LM: composable decoder-only / encoder-decoder model over LayerSpecs.

Layers are grouped into repeating units (the minimal period of the layer
pattern, e.g. jamba's 8-layer Mamba/attn block or gemma3's 6-layer
local:global cycle); each group's parameters are stacked on a leading
repeat axis and applied with ``lax.scan``. This keeps HLO size O(unit) and
lets ZeRO-3 gather one unit's weights at a time: the optional ``gather``
hook (path, leaf, salt) -> leaf is applied to every parameter leaf at its
point of use — identity for single-host runs, the quantized-VJP FSDP gather
in distributed training.

The cross-entropy loss is computed in sequence chunks (logits for the full
vocab are never materialized for the whole sequence at once).
"""
from __future__ import annotations

import dataclasses
import math
import zlib
from functools import partial
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.blocks import (LayerSpec, apply_layer_decode,
                                 apply_layer_prefill_chunk,
                                 apply_layer_train, attn_spec,
                                 init_layer, init_layer_cache)
from repro.models.layers import (dense_init, embed_init, layer_norm,
                                 rms_norm, shard, softcap)

GatherFn = Callable[[str, jnp.ndarray, Any], jnp.ndarray]


@dataclasses.dataclass(frozen=True)
class GroupSpec:
    unit: Tuple[LayerSpec, ...]
    repeats: int
    start: int          # global index of the group's first layer


def _identity_gather(path, leaf, salt):
    del path, salt
    return leaf


def build_layer_specs(cfg: ModelConfig, *, decoder: bool = True):
    specs = []
    for i in range(cfg.num_layers):
        specs.append(LayerSpec(
            kind=cfg.layer_kind(i),
            moe=cfg.layer_is_moe(i),
            d_ff=cfg.layer_ff(i),
            cross_attn=decoder and cfg.encoder is not None,
            causal=decoder,
        ))
    return specs


def build_groups(cfg: ModelConfig, specs) -> Tuple[GroupSpec, ...]:
    groups = []
    i = 0
    if cfg.first_layer_dense_ff:
        groups.append(GroupSpec(unit=(specs[0],), repeats=1, start=0))
        i = 1
    P = math.lcm(len(cfg.layer_pattern), cfg.moe_every or 1)
    main = len(specs) - i
    n_rep, rem = divmod(main, P)
    if n_rep:
        groups.append(GroupSpec(unit=tuple(specs[i:i + P]), repeats=n_rep,
                                start=i))
    if rem:
        start = i + n_rep * P
        groups.append(GroupSpec(unit=tuple(specs[start:]), repeats=1,
                                start=start))
    return tuple(groups)


def _path_salt(path: str) -> int:
    return zlib.crc32(path.encode())


class LM:
    """Decoder-only (or encoder-decoder, if cfg.encoder) language model."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.specs = build_layer_specs(cfg)
        self.groups = build_groups(cfg, self.specs)
        if cfg.encoder:
            enc_cfg = dataclasses.replace(
                cfg, num_layers=cfg.encoder.num_layers, moe_every=0,
                layer_pattern=("attn",), first_layer_dense_ff=0)
            self.enc_cfg = enc_cfg
            self.enc_specs = build_layer_specs(enc_cfg, decoder=False)
            self.enc_groups = build_groups(enc_cfg, self.enc_specs)

    # ------------------------------------------------------------------
    # init
    # ------------------------------------------------------------------
    def _init_group(self, cfg, group: GroupSpec, key):
        out = {}
        for j, spec in enumerate(group.unit):
            keys = jax.random.split(jax.random.fold_in(key, j),
                                    group.repeats)
            out[f"pos{j}"] = jax.vmap(
                lambda k: init_layer(cfg, spec, k))(keys)
        return out

    def init(self, key) -> dict:
        cfg = self.cfg
        ks = jax.random.split(key, 8)
        params = {
            "embed": embed_init(ks[0], (cfg.vocab_size, cfg.d_model)),
            "groups": tuple(
                self._init_group(cfg, g, jax.random.fold_in(ks[1], gi))
                for gi, g in enumerate(self.groups)),
            "final_norm": jnp.zeros((cfg.d_model,), jnp.float32)
            if cfg.norm == "rms" else
            {"scale": jnp.ones((cfg.d_model,), jnp.float32),
             "bias": jnp.zeros((cfg.d_model,), jnp.float32)},
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = dense_init(ks[2],
                                           (cfg.d_model, cfg.vocab_size))
        if cfg.encoder:
            params["encoder"] = {
                "pos_embed": embed_init(ks[3], (cfg.encoder.num_frames,
                                                cfg.d_model)),
                "groups": tuple(
                    self._init_group(self.enc_cfg, g,
                                     jax.random.fold_in(ks[4], gi))
                    for gi, g in enumerate(self.enc_groups)),
                "final_norm": {"scale": jnp.ones((cfg.d_model,), jnp.float32),
                               "bias": jnp.zeros((cfg.d_model,), jnp.float32)}
                if cfg.norm == "ln" else jnp.zeros((cfg.d_model,),
                                                   jnp.float32),
            }
        return params

    # ------------------------------------------------------------------
    # shared machinery
    # ------------------------------------------------------------------
    def _final_norm(self, p, x):
        cfg = self.cfg
        if cfg.norm == "ln":
            return layer_norm(x, p["scale"], p["bias"], cfg.norm_eps)
        return rms_norm(x, p, cfg.norm_eps)

    compute_dtype = jnp.bfloat16

    def _cast(self, leaf):
        if jnp.issubdtype(leaf.dtype, jnp.floating):
            return leaf.astype(self.compute_dtype)
        return leaf

    def _gather_leaf(self, path, leaf, salt, gather: GatherFn):
        return self._cast(gather(path, leaf, salt))

    def _gather_tree(self, tree, gather: GatherFn, prefix: str, salt):
        return jax.tree_util.tree_map_with_path(
            lambda path, leaf: self._gather_leaf(
                prefix + jax.tree_util.keystr(path), leaf, salt, gather),
            tree)

    def _run_groups(self, cfg, groups, group_params, x, gather: GatherFn,
                    enc_out=None, prefix=""):
        aux_total = jnp.float32(0)
        for gi, (g, gp) in enumerate(zip(groups, group_params)):
            gname = f"{prefix}g{gi}/"

            def body(carry, xs):
                x, aux = carry
                unit_p, idx = xs
                for j, spec in enumerate(g.unit):
                    pj = self._gather_tree(unit_p[f"pos{j}"], gather,
                                           gname + f"pos{j}", idx)
                    x, a = apply_layer_train(cfg, spec, pj, x,
                                             enc_out=enc_out)
                    aux = aux + a
                # keep the scan carry (= the checkpointed residual)
                # sequence-parallel: seq over `model`, batch over dp
                x = shard(x, ("pod", "data"), "model", None)
                return (x, aux), None

            if cfg.remat:
                body = jax.checkpoint(body)
            (x, aux_total), _ = jax.lax.scan(
                body, (x, aux_total), (gp, jnp.arange(g.repeats)))
        return x, aux_total

    def param_paths(self, params):
        """Pytree of gather-path strings aligned with ``params`` — the exact
        strings the runtime gather hook receives, for sharding planners."""
        kstr = jax.tree_util.keystr

        def named(prefix, tree):
            return jax.tree_util.tree_map_with_path(
                lambda p, l: prefix + kstr(p), tree)

        def group_paths(groups_p, prefix=""):
            return tuple(
                {k: named(f"{prefix}g{gi}/{k}", gp[k]) for k in gp}
                for gi, gp in enumerate(groups_p))

        out = {
            "embed": "embed",
            "final_norm": named("final_norm", params["final_norm"]),
            "groups": group_paths(params["groups"]),
        }
        if "lm_head" in params:
            out["lm_head"] = "lm_head"
        if "encoder" in params:
            enc = params["encoder"]
            out["encoder"] = {
                "pos_embed": "enc/['pos_embed']",
                "final_norm": named("enc/['final_norm']",
                                    enc["final_norm"]),
                "groups": group_paths(enc["groups"], "enc/"),
            }
        return out

    # ------------------------------------------------------------------
    # encoder (whisper; frontend stub supplies frame embeddings)
    # ------------------------------------------------------------------
    def encode(self, params, enc_embeds, gather: GatherFn = _identity_gather):
        cfg = self.cfg
        ep = self._gather_tree(
            {"pos_embed": params["encoder"]["pos_embed"],
             "final_norm": params["encoder"]["final_norm"]},
            gather, "enc/", 0)
        x = enc_embeds.astype(jnp.bfloat16) + ep["pos_embed"][None].astype(
            jnp.bfloat16)
        x, _ = self._run_groups(self.enc_cfg, self.enc_groups,
                                params["encoder"]["groups"], x, gather,
                                prefix="enc/")
        return self._final_norm(ep["final_norm"], x)

    # ------------------------------------------------------------------
    # training / prefill forward
    # ------------------------------------------------------------------
    def hidden_states(self, params, tokens,
                      gather: GatherFn = _identity_gather,
                      enc_embeds=None):
        cfg = self.cfg
        embed = self._gather_leaf("embed", params["embed"], 0, gather)
        x = jnp.take(embed, tokens, axis=0).astype(jnp.bfloat16)
        if cfg.embed_scale:
            x = x * jnp.bfloat16(math.sqrt(cfg.d_model))
        # sequence-parallel activation layout: batch over dp, seq over model
        # (inside shard_map the dp axes are manual and silently dropped)
        x = shard(x, ("pod", "data"), "model", None)
        enc_out = None
        if cfg.encoder:
            enc_out = self.encode(params, enc_embeds, gather)
        x, aux = self._run_groups(cfg, self.groups, params["groups"], x,
                                  gather, enc_out=enc_out)
        fp = self._gather_tree(params["final_norm"], gather, "final_norm", 0)
        return self._final_norm(fp, x), aux

    def _head(self, params, gather: GatherFn):
        if self.cfg.tie_embeddings:
            return self._gather_leaf("embed", params["embed"], 0, gather).T
        return self._gather_leaf("lm_head", params["lm_head"], 0, gather)

    def logits(self, params, tokens, gather: GatherFn = _identity_gather,
               enc_embeds=None):
        x, aux = self.hidden_states(params, tokens, gather, enc_embeds)
        head = self._head(params, gather)
        lg = (x @ head.astype(x.dtype)).astype(jnp.float32)
        return softcap(lg, self.cfg.final_softcap), aux

    @jax.named_scope("model")
    def loss(self, params, batch, gather: GatherFn = _identity_gather,
             *, loss_chunk: int = 512):
        """batch: {tokens (B,S) [, enc_embeds (B,F,D)]}. Next-token xent,
        computed in sequence chunks so (B,S,V) never materializes. Runs
        under the ``model`` named scope: differentiated, its forward ops
        carry ``jvp(model)`` and its backward ``transpose(jvp(model))``."""
        cfg = self.cfg
        tokens = batch["tokens"]
        B, S = tokens.shape
        x, aux = self.hidden_states(params, tokens, gather,
                                    batch.get("enc_embeds"))
        head = self._head(params, gather).astype(x.dtype)

        inputs = x[:, :-1]
        targets = tokens[:, 1:]
        T = inputs.shape[1]
        ck = min(loss_chunk, T)
        nc = -(-T // ck)
        pad = nc * ck - T
        inputs = jnp.pad(inputs, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)),
                          constant_values=-1)
        inputs = inputs.reshape(B, nc, ck, -1).swapaxes(0, 1)
        targets = targets.reshape(B, nc, ck).swapaxes(0, 1)

        def chunk_loss(carry, xs):
            xc, tc = xs                                 # (B,ck,D), (B,ck)
            lg = (xc @ head).astype(jnp.float32)
            lg = softcap(lg, cfg.final_softcap)
            lse = jax.nn.logsumexp(lg, axis=-1)
            tgt = jnp.take_along_axis(
                lg, jnp.maximum(tc, 0)[..., None], axis=-1)[..., 0]
            valid = (tc >= 0).astype(jnp.float32)
            nll = (lse - tgt) * valid
            tot, cnt = carry
            return (tot + nll.sum(), cnt + valid.sum()), None

        body = chunk_loss
        if cfg.remat:
            body = jax.checkpoint(body)
        (tot, cnt), _ = jax.lax.scan(body, (jnp.float32(0), jnp.float32(0)),
                                     (inputs, targets))
        loss = tot / jnp.maximum(cnt, 1.0)
        return loss + aux, {"nll": loss, "aux": aux, "tokens": cnt}

    # ------------------------------------------------------------------
    # decode
    # ------------------------------------------------------------------
    def init_cache(self, batch: int, max_len: int, dtype=jnp.bfloat16):
        cfg = self.cfg
        caches = []
        frames = cfg.encoder.num_frames if cfg.encoder else 0
        for g in self.groups:
            gc = {}
            for j, spec in enumerate(g.unit):
                one = init_layer_cache(cfg, spec, batch, max_len, dtype,
                                       enc_frames=frames)
                gc[f"pos{j}"] = jax.tree_util.tree_map(
                    lambda x: jnp.broadcast_to(
                        x[None], (g.repeats,) + x.shape), one)
            caches.append(gc)
        return tuple(caches)

    def warm_cache(self, params, cache, enc_embeds,
                   gather: GatherFn = _identity_gather):
        """Precompute whisper cross-attention K/V from the encoder output."""
        if not self.cfg.encoder:
            return cache
        from repro.models.blocks import _gqa_project  # noqa: PLC0415
        enc_out = self.encode(params, enc_embeds, gather)
        new = []
        for gi, (g, gp, gc) in enumerate(
                zip(self.groups, params["groups"], cache)):
            gcn = dict(gc)
            for j, spec in enumerate(g.unit):
                if not spec.cross_attn:
                    continue

                def per_rep(unit_p):
                    _, k, v = _gqa_project(self.cfg, unit_p["xattn"],
                                           enc_out)
                    return k, v

                ks, vs = jax.vmap(per_rep)(gp[f"pos{j}"])
                cj = dict(gcn[f"pos{j}"])
                cj["xk"] = ks.astype(cj["xk"].dtype)
                cj["xv"] = vs.astype(cj["xv"].dtype)
                gcn[f"pos{j}"] = cj
            new.append(gcn)
        return tuple(new)

    def decode_step(self, params, cache, tokens, pos,
                    gather: GatherFn = _identity_gather):
        """tokens (B, 1) int32, pos scalar int32 -> (logits (B,1,V), cache)."""
        cfg = self.cfg
        embed = self._gather_leaf("embed", params["embed"], 0, gather)
        x = jnp.take(embed, tokens, axis=0).astype(jnp.bfloat16)
        if cfg.embed_scale:
            x = x * jnp.bfloat16(math.sqrt(cfg.d_model))
        new_caches = []
        for gi, (g, gp, gc) in enumerate(
                zip(self.groups, params["groups"], cache)):
            gname = f"g{gi}/"

            def body(x, xs):
                unit_p, unit_c, idx = xs
                ncs = {}
                for j, spec in enumerate(g.unit):
                    pj = self._gather_tree(unit_p[f"pos{j}"], gather,
                                           gname + f"pos{j}", idx)
                    x, nc = apply_layer_decode(cfg, spec, pj, x,
                                               unit_c[f"pos{j}"], pos)
                    ncs[f"pos{j}"] = nc
                return x, ncs

            x, nc = jax.lax.scan(body, x, (gp, gc, jnp.arange(g.repeats)))
            new_caches.append(nc)
        fp = self._gather_tree(params["final_norm"], gather, "final_norm", 0)
        x = self._final_norm(fp, x)
        head = self._head(params, gather)
        lg = (x @ head.astype(x.dtype)).astype(jnp.float32)
        return softcap(lg, cfg.final_softcap), tuple(new_caches)

    def supports_chunked_prefill(self) -> bool:
        """True when every layer has the chunked-prefill path (GQA
        attention kinds only — mamba/rwkv/MLA fall back to the
        token-by-token ``prefill`` loop)."""
        cfg = self.cfg
        return (cfg.mla is None
                and all(s.kind in ("attn", "attn_local") for s in self.specs))

    def prefill_chunk(self, params, cache, tokens, start,
                      gather: GatherFn = _identity_gather):
        """Chunked prefill: tokens (B, T) at absolute positions
        start..start+T-1 -> (logits (B, T, V), cache). One forward over
        the chunk instead of T decode steps; the caller guarantees
        start+T fits every layer's cache (no ring wrap — for attn_local
        layers the cache must cover the full sequence)."""
        cfg = self.cfg
        embed = self._gather_leaf("embed", params["embed"], 0, gather)
        x = jnp.take(embed, tokens, axis=0).astype(jnp.bfloat16)
        if cfg.embed_scale:
            x = x * jnp.bfloat16(math.sqrt(cfg.d_model))
        new_caches = []
        for gi, (g, gp, gc) in enumerate(
                zip(self.groups, params["groups"], cache)):
            gname = f"g{gi}/"

            def body(x, xs):
                unit_p, unit_c, idx = xs
                ncs = {}
                for j, spec in enumerate(g.unit):
                    pj = self._gather_tree(unit_p[f"pos{j}"], gather,
                                           gname + f"pos{j}", idx)
                    x, nc = apply_layer_prefill_chunk(
                        cfg, spec, pj, x, unit_c[f"pos{j}"], start)
                    ncs[f"pos{j}"] = nc
                return x, ncs

            x, nc = jax.lax.scan(body, x, (gp, gc, jnp.arange(g.repeats)))
            new_caches.append(nc)
        fp = self._gather_tree(params["final_norm"], gather, "final_norm", 0)
        x = self._final_norm(fp, x)
        head = self._head(params, gather)
        lg = (x @ head.astype(x.dtype)).astype(jnp.float32)
        return softcap(lg, cfg.final_softcap), tuple(new_caches)

    def prefill(self, params, cache, tokens,
                gather: GatherFn = _identity_gather, enc_embeds=None):
        """Sequential prefill via decode_step (reference path for tests and
        small-model serving; production prefill lowers the chunked forward)."""
        if self.cfg.encoder:
            cache = self.warm_cache(params, cache, enc_embeds, gather)
        B, S = tokens.shape

        def step(carry, i):
            cache, _ = carry
            lg, cache = self.decode_step(params, cache, tokens[:, i][:, None],
                                         i, gather)
            return (cache, lg), None

        lg0 = jnp.zeros((B, 1, self.cfg.vocab_size), jnp.float32)
        (cache, lg), _ = jax.lax.scan(step, (cache, lg0), jnp.arange(S))
        return lg, cache
