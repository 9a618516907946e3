"""Mixture-of-Experts layer with expert parallelism over a mesh axis.

TPU-first design: top-k routing with a static per-rank capacity (so every
shape is fixed under jit — dropped tokens are the standard price for a
compiled dispatch), einsum-built dispatch/combine tensors (MXU-friendly,
no scatters), and ONE ``lax.all_to_all`` each way over the ``ep`` axis —
the same alltoall the ACCL surface exposes as a collective
(accl.alltoall / moveengine.expand_alltoall).

The expert FFN is the Llama SwiGLU block with a leading experts axis,
sharded over ``ep`` so each rank computes only its resident experts.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    dim: int = 64
    ffn_dim: int = 128
    n_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    def capacity(self, tokens: int) -> int:
        """Static per-rank expert capacity for a token count."""
        return max(1, int(np.ceil(
            tokens * self.top_k * self.capacity_factor / self.n_experts)))


class MoELayer:
    """Functional MoE FFN: router + E SwiGLU experts."""

    def __init__(self, config: MoEConfig):
        self.config = config

    def init(self, key: jax.Array) -> dict:
        c = self.config
        kr, kg, ku, kd = jax.random.split(key, 4)
        E, d, f = c.n_experts, c.dim, c.ffn_dim

        def dense(key, fan_in, *shape):
            return (jax.random.normal(key, shape, c.param_dtype)
                    * (fan_in ** -0.5))

        return {
            "router": dense(kr, d, d, E),
            "w_gate": dense(kg, d, E, d, f),
            "w_up": dense(ku, d, E, d, f),
            "w_down": dense(kd, f, E, f, d),
        }

    def param_specs(self, ep: str = "ep") -> dict:
        return {"router": P(None, None), "w_gate": P(ep, None, None),
                "w_up": P(ep, None, None), "w_down": P(ep, None, None)}

    # -- routing -----------------------------------------------------------
    def _route(self, params: dict, x: jnp.ndarray, capacity: int):
        """Build dispatch/combine tensors for tokens x: (T, d).

        Returns (dispatch (T, E, C) bool-ish, combine (T, E, C) float,
        aux_loss scalar)."""
        c = self.config
        E, k = c.n_experts, c.top_k
        logits = x.astype(jnp.float32) @ params["router"].astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)          # (T, E)
        vals, idx = lax.top_k(probs, k)                   # (T, k)
        sel = jax.nn.one_hot(idx, E, dtype=jnp.float32)   # (T, k, E)
        mask = jnp.sum(sel, axis=1)                       # (T, E) in {0,1}
        gates = mask * probs / jnp.maximum(
            jnp.sum(vals, axis=-1, keepdims=True), 1e-9)  # renormalized
        # position of each token in its expert's queue (first-come order)
        pos = jnp.cumsum(mask, axis=0) - mask             # (T, E)
        keep = (pos < capacity) * mask
        dispatch = jax.nn.one_hot(pos.astype(jnp.int32), capacity,
                                  dtype=jnp.float32) * keep[..., None]
        combine = dispatch * gates[..., None]
        # load-balancing aux loss (Switch-style): E * mean_frac_tokens .
        # mean_frac_probs
        frac_tokens = jnp.mean(mask, axis=0)
        frac_probs = jnp.mean(probs, axis=0)
        aux = E * jnp.sum(frac_tokens * frac_probs) / c.top_k
        return dispatch, combine, aux

    def _expert_ffn(self, params: dict, t: jnp.ndarray) -> jnp.ndarray:
        """t: (E_local, N, d) -> (E_local, N, d), SwiGLU per expert."""
        c = self.config
        wg = params["w_gate"].astype(c.dtype)
        wu = params["w_up"].astype(c.dtype)
        wd = params["w_down"].astype(c.dtype)
        t = t.astype(c.dtype)
        gate = jax.nn.silu(jnp.einsum("end,edf->enf", t, wg))
        up = jnp.einsum("end,edf->enf", t, wu)
        return jnp.einsum("enf,efd->end", gate * up, wd)

    # -- single-device reference ------------------------------------------
    def apply_dense(self, params: dict, x: jnp.ndarray,
                    capacity: int | None = None):
        """All experts local (the EP path must match this exactly when
        nothing exceeds capacity). x: (T, d)."""
        C = capacity or self.config.capacity(x.shape[0])
        dispatch, combine, aux = self._route(params, x, C)
        expert_in = jnp.einsum("tec,td->ecd", dispatch,
                               x.astype(jnp.float32))
        expert_out = self._expert_ffn(params, expert_in)
        out = jnp.einsum("tec,ecd->td", combine,
                         expert_out.astype(jnp.float32))
        return out.astype(x.dtype), aux

    # -- expert-parallel path ---------------------------------------------
    def apply_ep(self, params_local: dict, x: jnp.ndarray, axis_name: str,
                 capacity: int | None = None):
        """Inside shard_map: tokens sharded over ``axis_name`` (T_local, d);
        expert params carry only this rank's E/W experts."""
        c = self.config
        W = jax.lax.axis_size(axis_name)
        E = c.n_experts
        assert E % W == 0, f"{E} experts not divisible by ep={W}"
        E_loc = E // W
        C = capacity or c.capacity(x.shape[0])
        dispatch, combine, aux = self._route(params_local, x, C)
        # local dispatch (E, C, d) -> (W, E_loc, C, d) -> alltoall so each
        # rank receives every rank's slice for ITS experts
        expert_in = jnp.einsum("tec,td->ecd", dispatch,
                               x.astype(jnp.float32))
        expert_in = expert_in.reshape(W, E_loc, C, -1)
        expert_in = lax.all_to_all(expert_in, axis_name, split_axis=0,
                                   concat_axis=0, tiled=False)
        # (W, E_loc, C, d): axis 0 = originating rank; fold into tokens
        t = expert_in.transpose(1, 0, 2, 3).reshape(E_loc, W * C, -1)
        out = self._expert_ffn(params_local, t)
        out = out.reshape(E_loc, W, C, -1).transpose(1, 0, 2, 3)
        out = lax.all_to_all(out, axis_name, split_axis=0, concat_axis=0,
                             tiled=False)                  # back home
        out = out.reshape(E, C, -1)
        y = jnp.einsum("tec,ecd->td", combine, out.astype(jnp.float32))
        return y.astype(x.dtype), aux


@functools.lru_cache(maxsize=None)
def _ep_program(cfg: MoEConfig, mesh: Mesh, axis_name: str, capacity: int):
    lyr = MoELayer(cfg)
    pspec = lyr.param_specs(axis_name)
    xspec = P(axis_name, None)

    @functools.partial(jax.shard_map, mesh=mesh,
                       in_specs=(pspec, xspec), out_specs=(xspec, P()))
    def f(params, x):
        out, aux = lyr.apply_ep(params, x, axis_name, capacity)
        return out, lax.pmean(aux, axis_name)

    return jax.jit(f)


def moe_apply_sharded(layer: MoELayer, params: dict, x: jax.Array,
                      mesh: Mesh, axis_name: str = "ep",
                      capacity: int | None = None):
    """Global-array entry: x (T, d) token-sharded over ``axis_name``;
    expert params sharded on their leading axis. Returns (out, aux)."""
    W = mesh.shape[axis_name]
    C = capacity or layer.config.capacity(x.shape[0] // W)
    specs = layer.param_specs(axis_name)
    placed = {k: jax.device_put(v, NamedSharding(mesh, specs[k]))
              for k, v in params.items()}
    xs = jax.device_put(x, NamedSharding(mesh, P(axis_name, None)))
    prog = _ep_program(layer.config, mesh, axis_name, C)
    return prog(placed, xs)
