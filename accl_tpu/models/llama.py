"""Llama-family transformer, TPU-first (pure jax, GSPMD-sharded).

Design for the MXU/HBM/ICI (not a port of any torch code):
  * bfloat16 activations/params option, fp32 master weights + optimizer.
  * static shapes, no python control flow under jit; layers scanned.
  * GSPMD sharding: params and activations carry PartitionSpecs over a
    ('dp', 'tp') mesh (+ optional 'sp' sequence axis folded into dp for
    data, attention over tp heads). XLA inserts the all-gathers /
    reduce-scatters; bucketed DP gradient sync can instead be driven
    explicitly through accl_tpu collectives (the BASELINE config-5 path,
    benchmarks/configs.py:config5_llama_grads) to mirror the reference's
    ring-allreduce usage.

Shapes follow the Llama-3 family (GQA, SwiGLU, RoPE, RMSNorm);
``LlamaConfig.llama3_8b()`` reproduces the 8B geometry for BASELINE
config 5.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128_256
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    ffn_dim: int = 14_336
    max_seq_len: int = 8192
    rope_theta: float = 500_000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16      # activation/compute dtype (MXU-friendly)
    param_dtype: Any = jnp.float32
    # "flash": fused Pallas attention (ops.attention) — streaming KV,
    # native GQA (no repeated-KV copy), fused decode over the cache.
    # "dense": score-materializing einsum reference path. The GSPMD-
    # sharded forward uses flash too when a ``mesh`` is passed: a
    # shard_map over the tp head shards (sp None, head counts dividing
    # tp), or ring attention over the sp sequence shards (mesh + sp).
    # Sharded decode (forward_cached/generate with mesh) runs the fused
    # decode kernel per tp KV-head shard. Without a mesh, sharded paths
    # fall back to dense (a bare pallas_call has no GSPMD partitioning
    # rule).
    attention: str = "flash"
    # Mixture-of-experts FFN (Mixtral-style): n_experts > 0 replaces
    # every layer's SwiGLU with a top-k routed expert block
    # (models.moe.MoELayer math — static capacity, einsum dispatch);
    # the Switch-style load-balancing aux loss is added in loss() with
    # moe_aux_coef. 0 = dense FFN.
    n_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_aux_coef: float = 0.01

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @classmethod
    def llama3_8b(cls) -> "LlamaConfig":
        return cls()

    @classmethod
    def tiny(cls, vocab: int = 256, dim: int = 64, n_layers: int = 2,
             n_heads: int = 4, n_kv_heads: int = 2, ffn_dim: int = 128,
             max_seq_len: int = 128) -> "LlamaConfig":
        return cls(vocab_size=vocab, dim=dim, n_layers=n_layers,
                   n_heads=n_heads, n_kv_heads=n_kv_heads, ffn_dim=ffn_dim,
                   max_seq_len=max_seq_len)


def _rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps).astype(x.dtype)) * w


def _rope(x, positions, theta):
    """Rotary embedding; x: (..., seq, heads, head_dim)."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    angles = positions[..., None].astype(jnp.float32) * freqs  # (seq, hd/2)
    cos = jnp.cos(angles)[..., None, :]  # broadcast over heads
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


class Llama:
    """Functional Llama: params are a pytree dict; methods are pure.

    Layer params are stacked along a leading ``n_layers`` axis so the
    decoder runs as one ``lax.scan`` — one compiled layer body regardless of
    depth (fast compiles, XLA-friendly)."""

    def __init__(self, config: LlamaConfig):
        self.config = config

    # -- parameters --------------------------------------------------------
    def init(self, key: jax.Array) -> dict:
        c = self.config
        k_emb, k_layers, k_out = jax.random.split(key, 3)
        hd, nh, nkv = c.head_dim, c.n_heads, c.n_kv_heads

        def norm_init(*shape):
            return jnp.ones(shape, c.param_dtype)

        def dense(key, fan_in, *shape):
            return (jax.random.normal(key, shape, c.param_dtype)
                    * (fan_in ** -0.5))

        L = c.n_layers
        ks = jax.random.split(k_layers, 8)

        def stack(key, fan_in, *shape):
            return dense(key, fan_in, L, *shape)

        if c.n_experts:
            # one source of truth for the expert param layout: vmap
            # MoELayer.init over the layer axis (hand-duplicating its
            # shapes here would silently diverge on any MoE change)
            ffn = jax.vmap(self._moe_layer().init)(
                jax.random.split(ks[4], L))
        else:
            ffn = {
                "w_gate": stack(ks[4], c.dim, c.dim, c.ffn_dim),
                "w_up": stack(ks[5], c.dim, c.dim, c.ffn_dim),
                "w_down": stack(ks[6], c.ffn_dim, c.ffn_dim, c.dim),
            }
        params = {
            "embed": dense(k_emb, c.dim, c.vocab_size, c.dim),
            "layers": {
                "attn_norm": norm_init(L, c.dim),
                "wq": stack(ks[0], c.dim, c.dim, nh * hd),
                "wk": stack(ks[1], c.dim, c.dim, nkv * hd),
                "wv": stack(ks[2], c.dim, c.dim, nkv * hd),
                "wo": stack(ks[3], nh * hd, nh * hd, c.dim),
                "mlp_norm": norm_init(L, c.dim),
                **ffn,
            },
            "final_norm": norm_init(c.dim),
            "lm_head": dense(k_out, c.dim, c.dim, c.vocab_size),
        }
        return params

    def _moe_layer(self):
        from .moe import MoEConfig, MoELayer
        c = self.config
        return MoELayer(MoEConfig(
            dim=c.dim, ffn_dim=c.ffn_dim, n_experts=c.n_experts,
            top_k=c.moe_top_k, capacity_factor=c.moe_capacity_factor,
            dtype=c.dtype, param_dtype=c.param_dtype))

    def _ffn(self, h, p):
        """The per-layer FFN on normed activations h (B, S, D): SwiGLU,
        or the routed expert block when n_experts > 0. Returns
        (out (B, S, D), aux scalar)."""
        c = self.config
        if not c.n_experts:
            gate = jax.nn.silu(h @ p["w_gate"].astype(h.dtype))
            up = h @ p["w_up"].astype(h.dtype)
            return (gate * up) @ p["w_down"].astype(h.dtype), jnp.zeros(
                (), jnp.float32)
        B, S, D = h.shape
        layer = self._moe_layer()
        mparams = {k: p[k] for k in ("router", "w_gate", "w_up", "w_down")}
        # route PER SEQUENCE (vmap over batch): dispatch/combine tensors
        # are O(group_tokens * E * capacity), so the group must be a
        # sequence, not the flattened global batch — at 8B-scale token
        # counts a flat group's dispatch tensor alone would not fit in
        # HBM. Expert-parallel sharding over an ep axis is the scale-out
        # form (models.moe.moe_apply_sharded).
        out, aux = jax.vmap(lambda t: layer.apply_dense(mparams, t))(h)
        return out, jnp.mean(aux)

    # -- sharding ----------------------------------------------------------
    def param_specs(self, dp: str = "dp", tp: str = "tp") -> dict:
        """PartitionSpecs for a (dp, tp) mesh: megatron-style TP — qkv/gate/
        up column-parallel, wo/down row-parallel, embeddings sharded on
        vocab. MoE expert weights (leading (L, E) axes) shard their
        per-expert matmul dims the same column/row-parallel way; the
        router is replicated."""
        if self.config.n_experts:
            ffn = {
                "router": P(None, None, None),
                "w_gate": P(None, None, None, tp),
                "w_up": P(None, None, None, tp),
                "w_down": P(None, None, tp, None),
            }
        else:
            ffn = {
                "w_gate": P(None, None, tp),
                "w_up": P(None, None, tp),
                "w_down": P(None, tp, None),
            }
        return {
            "embed": P(tp, None),
            "layers": {
                "attn_norm": P(None, None),
                "wq": P(None, None, tp),
                "wk": P(None, None, tp),
                "wv": P(None, None, tp),
                "wo": P(None, tp, None),
                "mlp_norm": P(None, None),
                **ffn,
            },
            "final_norm": P(None),
            "lm_head": P(None, tp),
        }

    def shard_params(self, params: dict, mesh: Mesh, dp: str = "dp",
                     tp: str = "tp") -> dict:
        specs = self.param_specs(dp, tp)
        return jax.tree.map(
            lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
            params, specs,
            is_leaf=lambda x: isinstance(x, (jnp.ndarray, np.ndarray)))

    # -- forward -----------------------------------------------------------
    def _layer(self, x, layer_params, positions, mask, use_flash=False,
               shard_ctx=None):
        c = self.config
        p = layer_params
        hd, nh, nkv = c.head_dim, c.n_heads, c.n_kv_heads
        B, S, D = x.shape

        h = _rms_norm(x, p["attn_norm"].astype(x.dtype), c.norm_eps)
        q = (h @ p["wq"].astype(x.dtype)).reshape(B, S, nh, hd)
        k = (h @ p["wk"].astype(x.dtype)).reshape(B, S, nkv, hd)
        v = (h @ p["wv"].astype(x.dtype)).reshape(B, S, nkv, hd)
        q = _rope(q, positions, c.rope_theta)
        k = _rope(k, positions, c.rope_theta)
        if use_flash:
            # fused path: KV heads stay un-repeated — the kernel's index
            # maps route each Q head to its KV head (GQA without the
            # max_len-sized repeat copy); differentiable (custom VJP)
            from ..ops.attention import flash_attention
            qt, kt, vt = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
            if shard_ctx is not None:
                # GSPMD sharded attention, one shard_map either way:
                # - "tp": heads are column-parallel; attention is
                #   embarrassingly parallel across head shards, so the
                #   fused kernel runs per shard.
                # - "sp": sequence sharded over the ring; ring attention
                #   rotates the (un-repeated GQA) KV blocks over ICI
                #   while each shard's Q accumulates — the long-context
                #   schedule, no full-sequence gather ever.
                # (check_vma=False: the pallas interpreter's internal
                # slices don't carry varying-axis types, ulysses parity)
                mode, mesh, dp_ax, ax = shard_ctx
                if mode == "tp":
                    spec = P(dp_ax, ax, None, None)
                    f = functools.partial(flash_attention, causal=True)
                else:
                    from ..parallel.ring_attention import ring_attention

                    spec = P(dp_ax, None, ax, None)
                    f = functools.partial(ring_attention, axis_name=ax,
                                          causal=True)
                attn = jax.shard_map(f, mesh=mesh,
                                     in_specs=(spec, spec, spec),
                                     out_specs=spec,
                                     check_vma=False)(qt, kt, vt)
            else:
                attn = flash_attention(qt, kt, vt, causal=True)
            attn = attn.transpose(0, 2, 1, 3).reshape(B, S, nh * hd)
        else:
            # GQA: repeat kv heads
            rep = nh // nkv
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)
            # attention (B, nh, S, hd)
            q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
            scores = jnp.einsum(
                "bhqd,bhkd->bhqk", q, k,
                preferred_element_type=jnp.float32) * (hd ** -0.5)
            scores = jnp.where(mask, scores, jnp.finfo(jnp.float32).min)
            probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
            attn = jnp.einsum("bhqk,bhkd->bhqd", probs, v)
            attn = attn.transpose(0, 2, 1, 3).reshape(B, S, nh * hd)
        x = x + attn @ p["wo"].astype(x.dtype)

        h = _rms_norm(x, p["mlp_norm"].astype(x.dtype), c.norm_eps)
        ffn_out, aux = self._ffn(h, p)
        x = x + ffn_out
        return x, aux

    def forward(self, params: dict, tokens: jnp.ndarray,
                dp: str | None = None, sp: str | None = None,
                mesh: Mesh | None = None, tp: str = "tp") -> jnp.ndarray:
        """Logits for (B, S) int32 tokens (see _forward_with_aux, which
        additionally returns the MoE load-balancing aux loss)."""
        return self._forward_with_aux(params, tokens, dp, sp, mesh, tp)[0]

    def _forward_with_aux(self, params: dict, tokens: jnp.ndarray,
                          dp: str | None = None, sp: str | None = None,
                          mesh: Mesh | None = None,
                          tp: str = "tp"):
        """Logits for (B, S) int32 tokens. When dp/sp axis names are given,
        activation sharding constraints pin batch->dp and seq->sp.

        With ``mesh`` also given, attention runs fused inside a
        shard_map: over the tp head shards when sp is None (requires the
        tp axis size to divide the head counts, GQA KV heads included), or
        as RING attention over the sp sequence shards when sp is given
        (un-repeated GQA KV on every hop, no full-sequence gather)."""
        c = self.config
        B, S = tokens.shape
        x = params["embed"].astype(c.dtype)[tokens]
        if dp is not None:
            x = jax.lax.with_sharding_constraint(x, P(dp, sp, None))
        positions = jnp.arange(S)
        shard_ctx = None
        if (c.attention == "flash" and dp is None and sp is None
                and mesh is None):
            # unsharded: the bare pallas_call. A passed mesh must NOT
            # land here — a bare pallas_call has no GSPMD partitioning
            # rule, so sharded operands need the shard_map tp branch.
            use_flash = True
        elif c.attention == "flash" and mesh is not None and sp is None:
            # tensor-parallel training: fused attention over the tp head
            # shards. Same loud-failure discipline as the sp branch
            # below (and as forward_cached): a silent dense fallback
            # would materialize the O(S^2) score tensor the fused path
            # exists to avoid.
            if tp not in mesh.shape:
                raise ValueError(
                    f"mesh given but tp axis {tp!r} is not in mesh "
                    f"{tuple(mesh.shape)}: name the model axis via tp=, "
                    "or omit mesh= for the unsharded fused kernel")
            if (c.n_heads % mesh.shape[tp]
                    or c.n_kv_heads % mesh.shape[tp]):
                raise ValueError(
                    f"tp axis size {mesh.shape[tp]} must divide the head "
                    f"counts (n_heads={c.n_heads}, "
                    f"n_kv_heads={c.n_kv_heads})")
            # (a dp name missing from the mesh already fails loudly at
            # the embedding's with_sharding_constraint; an INDIVISIBLE
            # batch traces through it fine and would only die later with
            # a cryptic shard_map divisibility error — catch it here)
            if dp is not None and dp in mesh.shape and B % mesh.shape[dp]:
                raise ValueError(
                    f"batch {B} not divisible by dp axis size "
                    f"{mesh.shape[dp]}")
            use_flash = True
            shard_ctx = ("tp", mesh, dp, tp)
        elif c.attention == "flash" and mesh is not None and sp is not None:
            # sequence-parallel training: ring attention over the sp
            # axis. A silent fallback to dense here would materialize
            # the O(S^2) score tensor sequence parallelism exists to
            # avoid — fail loudly when the request can't be honored.
            if sp not in mesh.shape:
                raise ValueError(f"sp axis {sp!r} not in mesh "
                                 f"{tuple(mesh.shape)}")
            if S % mesh.shape[sp]:
                raise ValueError(
                    f"sequence length {S} not divisible by sp axis size "
                    f"{mesh.shape[sp]} — ring attention needs equal "
                    "sequence shards")
            if dp is not None and dp not in mesh.shape:
                raise ValueError(f"dp axis {dp!r} not in mesh "
                                 f"{tuple(mesh.shape)}")
            if dp is not None and B % mesh.shape[dp]:
                raise ValueError(
                    f"batch {B} not divisible by dp axis size "
                    f"{mesh.shape[dp]}")
            use_flash = True
            shard_ctx = ("sp", mesh, dp, sp)
        else:
            use_flash = False
        # dense needs the materialized mask; the flash kernel masks
        # blockwise in VMEM
        mask = (None if use_flash
                else jnp.tril(jnp.ones((S, S), bool))[None, None])

        def body(x, layer_params):
            x, aux = self._layer(x, layer_params, positions, mask,
                                 use_flash, shard_ctx)
            return x, aux

        x, auxes = jax.lax.scan(body, x, params["layers"])
        x = _rms_norm(x, params["final_norm"].astype(x.dtype), c.norm_eps)
        logits = x @ params["lm_head"].astype(c.dtype)
        return logits.astype(jnp.float32), jnp.sum(auxes)

    # -- inference: KV-cache decode ----------------------------------------
    def init_kv_cache(self, batch: int, max_len: int, dtype=None) -> dict:
        """Preallocated static-shape KV cache: (L, B, n_kv, max_len, hd)
        per tensor + a scalar fill position. Head-major, so the decode
        kernel's (block, hd) tile per kv head is TPU-tileable; static
        shapes keep every decode step one compiled program."""
        c = self.config
        dt = dtype or c.dtype
        shape = (c.n_layers, batch, c.n_kv_heads, max_len, c.head_dim)
        return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt),
                "pos": jnp.zeros((), jnp.int32)}

    def _layer_cached(self, x, layer_params, kc, vc, pos,
                      shard_ctx=None):
        """One decoder layer over cached context: x holds S_new tokens at
        absolute positions pos..pos+S_new-1; kc/vc are (B, nkv, max_len, hd)
        and are updated in place (dynamic_update_slice). Returns
        (x, kc, vc)."""
        c = self.config
        p = layer_params
        hd, nh, nkv = c.head_dim, c.n_heads, c.n_kv_heads
        B, S, D = x.shape
        max_len = kc.shape[2]

        h = _rms_norm(x, p["attn_norm"].astype(x.dtype), c.norm_eps)
        positions = pos + jnp.arange(S)
        q = (h @ p["wq"].astype(x.dtype)).reshape(B, S, nh, hd)
        k = (h @ p["wk"].astype(x.dtype)).reshape(B, S, nkv, hd)
        v = (h @ p["wv"].astype(x.dtype)).reshape(B, S, nkv, hd)
        q = _rope(q, positions, c.rope_theta)
        k = _rope(k, positions, c.rope_theta)
        kc = jax.lax.dynamic_update_slice(
            kc, k.transpose(0, 2, 1, 3).astype(kc.dtype), (0, 0, pos, 0))
        vc = jax.lax.dynamic_update_slice(
            vc, v.transpose(0, 2, 1, 3).astype(vc.dtype), (0, 0, pos, 0))

        if self.config.attention == "flash":
            # fused decode kernel over the cache's native layout: cache
            # blocks past the fill (pos + S) are neither fetched nor
            # computed, so a step costs the filled prefix, not max_len
            from ..ops.attention import flash_decode
            qt = q.transpose(0, 2, 1, 3)
            if shard_ctx is not None:
                # tp decode: KV-head shards of the cache stay put; each
                # tp shard decodes its own head group with the fused
                # kernel (no cache gather, no repeated-KV copy)
                mesh, dp_ax, tp_ax = shard_ctx
                attn = jax.shard_map(
                    flash_decode,
                    mesh=mesh,
                    in_specs=(P(dp_ax, tp_ax, None, None),
                              P(dp_ax, tp_ax, None, None),
                              P(dp_ax, tp_ax, None, None), P()),
                    out_specs=P(dp_ax, tp_ax, None, None),
                    check_vma=False)(qt, kc, vc, pos + S)
            else:
                attn = flash_decode(qt, kc, vc, kv_len=pos + S)
            attn = attn.transpose(0, 2, 1, 3).reshape(B, S, nh * hd)
        else:
            # grouped-query attention without materializing repeated K/V
            # over max_len (that copy is the cost GQA exists to avoid):
            # fold the per-kv-head query group into the einsum instead
            rep = nh // nkv
            qg = q.reshape(B, S, nkv, rep, hd)        # (B, S, nkv, rep, hd)
            kt = kc.astype(x.dtype)                   # (B, nkv, max, hd)
            vt = vc.astype(x.dtype)
            scores = jnp.einsum(
                "bskrd,bktd->bkrst", qg, kt,
                preferred_element_type=jnp.float32) * (hd ** -0.5)
            kpos = jnp.arange(max_len)
            mask = kpos[None, :] <= positions[:, None]  # (S, max) causal
            scores = jnp.where(mask[None, None, None], scores,
                               jnp.finfo(jnp.float32).min)
            probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
            attn = jnp.einsum("bkrst,bktd->bskrd", probs, vt)
            attn = attn.reshape(B, S, nh * hd)
        x = x + attn @ p["wo"].astype(x.dtype)

        h = _rms_norm(x, p["mlp_norm"].astype(x.dtype), c.norm_eps)
        ffn_out, _aux = self._ffn(h, p)  # aux is a training-time signal
        x = x + ffn_out
        return x, kc, vc

    def forward_cached(self, params: dict, tokens: jnp.ndarray,
                       cache: dict, mesh: Mesh | None = None,
                       dp: str | None = None,
                       tp: str = "tp") -> tuple[jnp.ndarray, dict]:
        """Logits for S_new tokens appended at cache['pos'], plus the
        updated cache. Used for both prefill (S_new = prompt len) and
        decode (S_new = 1); jit once per S_new. With ``mesh`` given (and
        the tp axis size dividing the head counts), decode attention
        runs the fused kernel per tp KV-head shard — tensor-parallel
        inference without gathering the cache."""
        c = self.config
        x = params["embed"].astype(c.dtype)[tokens]
        pos = cache["pos"]
        shard_ctx = None
        if mesh is not None:
            # fail loudly (sp-path discipline): a silent fallback would
            # trace the bare pallas decode over sharded globals and XLA
            # would all-gather the ENTIRE cache to every device per step
            if c.attention != "flash":
                raise ValueError("mesh-sharded decode requires "
                                 "attention='flash'")
            if tp not in mesh.shape:
                raise ValueError(f"tp axis {tp!r} not in mesh "
                                 f"{tuple(mesh.shape)}")
            if c.n_heads % mesh.shape[tp] or c.n_kv_heads % mesh.shape[tp]:
                raise ValueError(
                    f"tp axis size {mesh.shape[tp]} must divide the "
                    f"head counts ({c.n_heads} q / {c.n_kv_heads} kv) "
                    "for sharded decode")
            if dp is not None and dp not in mesh.shape:
                raise ValueError(f"dp axis {dp!r} not in mesh "
                                 f"{tuple(mesh.shape)}")
            if dp is not None and tokens.shape[0] % mesh.shape[dp]:
                raise ValueError(
                    f"batch {tokens.shape[0]} not divisible by dp axis "
                    f"size {mesh.shape[dp]}")
            shard_ctx = (mesh, dp, tp)

        def body(xc, layer):
            x = xc
            lp, kc, vc = layer
            x, kc, vc = self._layer_cached(x, lp, kc, vc, pos, shard_ctx)
            return x, (kc, vc)

        x, (knew, vnew) = jax.lax.scan(
            body, x, (params["layers"], cache["k"], cache["v"]))
        x = _rms_norm(x, params["final_norm"].astype(x.dtype), c.norm_eps)
        logits = (x @ params["lm_head"].astype(c.dtype)).astype(jnp.float32)
        new_cache = {"k": knew, "v": vnew,
                     "pos": pos + tokens.shape[1]}
        return logits, new_cache

    def generate(self, params: dict, prompt: jnp.ndarray, max_new: int,
                 max_len: int | None = None,
                 temperature: float = 0.0,
                 key: jax.Array | None = None,
                 mesh: Mesh | None = None,
                 dp: str | None = None, tp: str = "tp") -> jnp.ndarray:
        """Greedy (or temperature) decode: prefill the prompt, then one
        jitted single-token step per new token. Returns (B, max_new)."""
        B, S = prompt.shape
        max_len = max_len or (S + max_new)
        # the last sampled token is never stepped, so S + max_new - 1 cache
        # slots are written; a short cache would silently clamp
        # dynamic_update_slice and corrupt attention instead of erroring
        if max_len < S + max_new - 1:
            raise ValueError(
                f"max_len={max_len} too small for prompt {S} + "
                f"{max_new - 1} cached decode steps")
        cache = self.init_kv_cache(B, max_len)
        # one cached jit serves prefill and decode (distinct trace-cache
        # entries per S_new); rebuilding wrappers per call would recompile
        step = self._jit_forward_cached()
        if mesh is not None:
            step = functools.partial(step, mesh=mesh, dp=dp, tp=tp)
        logits, cache = step(params, prompt, cache)
        out = []
        last = logits[:, -1]
        if key is None:
            key = jax.random.key(0)
        for i in range(max_new):
            if temperature > 0:
                key, sub = jax.random.split(key)
                tok = jax.random.categorical(sub, last / temperature, axis=-1)
            else:
                tok = jnp.argmax(last, axis=-1)
            out.append(tok)
            if i + 1 < max_new:  # the last sampled token needs no step
                logits, cache = step(params, tok[:, None], cache)
                last = logits[:, -1]
        return jnp.stack(out, axis=1)

    def _jit_forward_cached(self):
        fn = getattr(self, "_fc_jit", None)
        if fn is None:
            fn = jax.jit(self.forward_cached,
                         static_argnames=("mesh", "dp", "tp"))
            self._fc_jit = fn
        return fn

    def loss(self, params: dict, tokens: jnp.ndarray,
             dp: str | None = None, sp: str | None = None,
             mesh: Mesh | None = None, tp: str = "tp") -> jnp.ndarray:
        """Next-token cross entropy (mean over B, S-1), plus the MoE
        load-balancing aux term scaled by moe_aux_coef when experts are
        enabled."""
        logits, aux = self._forward_with_aux(params, tokens, dp, sp,
                                             mesh, tp)
        logits = logits[:, :-1]
        targets = tokens[:, 1:]
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = jnp.mean(-jnp.take_along_axis(logp, targets[..., None],
                                            axis=-1))
        if self.config.n_experts:
            nll = nll + self.config.moe_aux_coef * aux
        return nll

    # -- training ----------------------------------------------------------
    def make_train_step(self, optimizer, dp: str | None = None,
                        sp: str | None = None,
                        mesh: Mesh | None = None, tp: str = "tp"):
        """Returns train_step(params, opt_state, tokens) -> (params,
        opt_state, loss). Pure; jit/pjit outside. Pass ``mesh`` to run
        attention as the fused flash kernel over tp head shards."""

        def train_step(params, opt_state, tokens):
            loss, grads = jax.value_and_grad(self.loss)(
                params, tokens, dp, sp, mesh, tp)
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = jax.tree.map(lambda p, u: p + u, params, updates)
            return params, opt_state, loss

        return train_step

    def param_count(self, params: dict) -> int:
        return sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))

    def grad_buckets(self, params: dict, bucket_bytes: int = 25 << 20
                     ) -> list[list[str]]:
        """Group parameter leaves into ~bucket_bytes buckets (DDP-style
        bucketed gradient all-reduce; BASELINE config 5). Returns lists of
        pytree key-paths, in reverse layer order like bucketed DDP."""
        leaves = jax.tree_util.tree_leaves_with_path(params)
        buckets, cur, cur_bytes = [], [], 0
        for path, leaf in reversed(leaves):
            key = jax.tree_util.keystr(path)
            nbytes = int(np.prod(leaf.shape)) * 4
            cur.append(key)
            cur_bytes += nbytes
            if cur_bytes >= bucket_bytes:
                buckets.append(cur)
                cur, cur_bytes = [], 0
        if cur:
            buckets.append(cur)
        return buckets
