"""Mixture-of-Experts layer: sort-based token dispatch, EP via all_to_all.

Dispatch is *local-first* (the Bind lesson applied to MoE): each mesh shard
sorts only its own tokens (a few-thousand-element argsort instead of a
global multi-million one, which XLA cannot partition), builds a fixed
capacity (E, C, d) buffer, and only then communicates:

* ``ep`` mode (experts % model_size == 0, e.g. moonshot 64/16): the buffer's
  expert axis all_to_all's over the model axis — each shard receives its
  experts' tokens from every peer, applies them, and all_to_all's back.
* ``replicated`` mode (granite's 40 experts don't divide 16): every shard
  holds all (tiny) experts and applies them to its local sequence slice —
  zero MoE collectives; expert weights stay FSDP-sharded at rest.

Fixed capacity C = ceil(T_local·k/E · capacity_factor); overflow tokens drop
(standard Switch-style), underflow pads — keeping all_to_all sizes static.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from jax.sharding import PartitionSpec as P

from repro.sharding.constraints import current_policy
from .layers import dense_init


def init_moe(key, cfg, dtype) -> dict:
    E, d, ff = cfg.n_experts, cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 4)
    s_in, s_ff = 1.0 / math.sqrt(d), 1.0 / math.sqrt(ff)
    return {
        "router": (jax.random.normal(ks[0], (d, E)) * s_in).astype(jnp.float32),
        "experts": {
            "w_gate": (jax.random.normal(ks[1], (E, d, ff)) * s_in).astype(dtype),
            "w_up": (jax.random.normal(ks[2], (E, d, ff)) * s_in).astype(dtype),
            "w_down": (jax.random.normal(ks[3], (E, ff, d)) * s_ff).astype(dtype),
        },
    }


def _capacity(t_local: int, cfg) -> int:
    c = math.ceil(t_local * cfg.n_experts_active / cfg.n_experts
                  * cfg.capacity_factor)
    return max(4, c)


def _dispatch(x, top_i, top_w, E: int, C: int):
    """Build the (E, C, d) buffer + combine metadata from local tokens."""
    T, d = x.shape
    k = top_i.shape[1]
    flat_e = top_i.reshape(-1)                       # (T*k,)
    sort_idx = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[sort_idx]
    first = jnp.searchsorted(sorted_e, jnp.arange(E))
    pos = jnp.arange(T * k) - first[sorted_e]
    valid = pos < C
    slot = jnp.where(valid, sorted_e * C + pos, E * C)   # E*C = trash row
    token_idx = sort_idx // k
    buf = jnp.zeros((E * C + 1, d), x.dtype)
    buf = buf.at[slot].set(x[token_idx] * valid[:, None].astype(x.dtype))
    meta = (slot, token_idx, top_w.reshape(-1)[sort_idx], valid)
    return buf[: E * C].reshape(E, C, d), meta


def _combine(expert_out, meta, T: int):
    E, C, d = expert_out.shape
    slot, token_idx, w, valid = meta
    flat = jnp.concatenate(
        [expert_out.reshape(E * C, d), jnp.zeros((1, d), expert_out.dtype)])
    vals = flat[slot] * (w * valid).astype(expert_out.dtype)[:, None]
    return jnp.zeros((T, d), expert_out.dtype).at[token_idx].add(vals)


def _expert_ffn(experts, buf, mlp_kind: str):
    """(E, C, d) × expert weights -> (E, C, d)."""
    act = jax.nn.silu if mlp_kind == "swiglu" else (
        lambda z: jax.nn.gelu(z, approximate=True))
    h = act(jnp.einsum("ecd,edf->ecf", buf, experts["w_gate"]))
    h = h * jnp.einsum("ecd,edf->ecf", buf, experts["w_up"])
    return jnp.einsum("ecf,efd->ecd", h, experts["w_down"])


def _route(p, x, cfg):
    logits = (x.astype(jnp.float32) @ p["router"])          # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_i = lax.top_k(probs, cfg.n_experts_active)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balance auxiliary loss
    dispatch_frac = jnp.zeros((cfg.n_experts,)).at[top_i.reshape(-1)].add(
        1.0) / (x.shape[0] * cfg.n_experts_active)
    mean_prob = probs.mean(axis=0)
    aux = cfg.n_experts * jnp.sum(dispatch_frac * mean_prob)
    return top_i, top_w, aux


def _moe_tokens_local(p, x, cfg, C: int):
    """All experts applied locally to local tokens (replicated mode)."""
    top_i, top_w, aux = _route(p, x, cfg)
    buf, meta = _dispatch(x, top_i, top_w, cfg.n_experts, C)
    out = _expert_ffn(p["experts"], buf, cfg.mlp)
    return _combine(out, meta, x.shape[0]), aux


def _moe_tokens_ep(p, x, cfg, C: int, axis: str):
    """EP: expert-sharded weights; token buffers exchanged via all_to_all."""
    top_i, top_w, aux = _route(p, x, cfg)
    buf, meta = _dispatch(x, top_i, top_w, cfg.n_experts, C)   # (E, C, d)
    # send each expert group to its owner shard; receive peers' tokens
    buf = lax.all_to_all(buf, axis, split_axis=0, concat_axis=1, tiled=True)
    out = _expert_ffn(p["experts"], buf, cfg.mlp)              # (E/n, n*C, d)
    out = lax.all_to_all(out, axis, split_axis=1, concat_axis=0, tiled=True)
    return _combine(out, meta, x.shape[0]), aux


def moe_layer(p: dict, x: jax.Array, cfg) -> tuple[jax.Array, jax.Array]:
    """(B, S, d) -> (B, S, d), aux_loss. Mesh-aware via the active policy."""
    b, s, d = x.shape
    pol = current_policy()
    if pol is None or pol.model_axis is None:
        t = b * s
        # decode (s==1): capacity = T so no token ever drops mid-generation
        C = t if s == 1 else _capacity(t, cfg)
        y, aux = _moe_tokens_local(p, x.reshape(t, d), cfg, C)
        return y.reshape(b, s, d), aux

    mesh = pol.mesh
    dp = pol.dp_axes if pol.batch_sharded else None
    sp = pol.model_axis if pol.seq_sharded else None
    x_spec = P(dp, sp, None)
    n_model = pol.model_size
    b_loc = b // pol.dp_size if pol.batch_sharded else b
    s_loc = s // n_model if pol.seq_sharded else s
    t_loc = b_loc * s_loc
    C = t_loc if s == 1 else _capacity(t_loc, cfg)
    ep = (cfg.moe_mode == "ep" and cfg.n_experts % n_model == 0
          and n_model > 1)

    all_axes = tuple(mesh.axis_names)
    if ep:
        e_spec = jax.tree_util.tree_map(
            lambda _: P(pol.model_axis, None, None), p["experts"])
        p_spec = {"router": P(None, None), "experts": e_spec}

        def run(pp, xx):
            y, aux = _moe_tokens_ep(
                pp, xx.reshape(t_loc, d), cfg, C, pol.model_axis)
            return y.reshape(xx.shape), lax.pmean(aux, all_axes)

        out_specs = (x_spec, P())
    else:
        p_spec = jax.tree_util.tree_map(lambda _: P(), p)

        def run(pp, xx):
            y, aux = _moe_tokens_local(pp, xx.reshape(t_loc, d), cfg, C)
            return y.reshape(xx.shape), lax.pmean(aux, all_axes)

        out_specs = (x_spec, P())

    y, aux = jax.shard_map(
        run, mesh=mesh, in_specs=(p_spec, x_spec), out_specs=out_specs,
        check_vma=False,
    )(p, x)
    return y, aux
