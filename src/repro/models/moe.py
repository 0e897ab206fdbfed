"""Mixture-of-Experts FFN with sort-based capacity dispatch (EP-shardable).

Implements the DeepSeekMoE / granite shape: optional shared experts that see
every token, plus E routed experts with top-k gating.  Dispatch is the
production "dropping" formulation:

  1. top-k routing per token, gate weights renormalized over the selected k;
  2. (token, expert) assignments sorted by expert id; each assignment gets a
     position-in-expert by cumulative count;
  3. assignments beyond per-expert capacity C are dropped (weight mass of
     dropped tokens is simply lost, as in GShard/Switch);
  4. kept tokens are scattered into an (E, C, d) buffer, experts run as one
     batched einsum, results scatter-added back per token.

FLOPs are proportional to the *routed* compute (E x C x d x ff), not to
E x T — this is what makes the MoE cells' roofline numbers honest.  The
(E, C, d) buffer carries the 'experts' logical axis, so EP sharding places
each expert's rows on its owner and XLA lowers the dispatch/return to
all-to-alls across the 'model' axis.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import layers

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_ff_expert: int
    n_shared: int = 0            # shared experts (DeepSeekMoE)
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.01   # load-balance aux loss (Switch)
    # physical expert padding so EP shards evenly (granite: 40 -> 48 over a
    # 16-way axis).  Pad experts' router logits are masked to -inf: they
    # receive no tokens and no gradient.
    pad_experts_to: Optional[int] = None
    # expert-parallel dispatch via shard_map (tokens never migrate; one
    # (t_local, d) psum per layer replaces the GSPMD scatter all-reduce of
    # the whole (E, C, d) buffer — the §Perf hillclimb for the MoE cells)
    ep_shard_map: bool = False

    @property
    def n_experts_padded(self) -> int:
        return self.pad_experts_to or self.n_experts

    def capacity(self, n_tokens: int) -> int:
        c = int(n_tokens * self.top_k * self.capacity_factor / self.n_experts)
        return max(8, -(-c // 8) * 8)  # pad to 8 for clean tiling


def init_moe_params(
    key: Array, d_model: int, cfg: MoEConfig
) -> Dict[str, Array]:
    ks = jax.random.split(key, 5)
    ep = cfg.n_experts_padded
    p = {
        "router": layers.dense_init(ks[0], (d_model, ep)),
        "w_gate": layers.dense_init(ks[1], (ep, d_model, cfg.d_ff_expert)),
        "w_up": layers.dense_init(ks[2], (ep, d_model, cfg.d_ff_expert)),
        "w_down": layers.dense_init(ks[3], (ep, cfg.d_ff_expert, d_model)),
    }
    if cfg.n_shared > 0:
        ff_sh = cfg.n_shared * cfg.d_ff_expert
        ksh = jax.random.split(ks[4], 3)
        p["shared_gate"] = layers.dense_init(ksh[0], (d_model, ff_sh))
        p["shared_up"] = layers.dense_init(ksh[1], (d_model, ff_sh))
        p["shared_down"] = layers.dense_init(ksh[2], (ff_sh, d_model))
    return p


def moe_param_specs(cfg: MoEConfig) -> Dict[str, Tuple]:
    """Logical axis names per parameter (leading 'layers' added by the LM)."""
    p = {
        "router": ("embed", "experts"),
        "w_gate": ("experts", "embed", "expert_mlp"),
        "w_up": ("experts", "embed", "expert_mlp"),
        "w_down": ("experts", "expert_mlp", "embed"),
    }
    if cfg.n_shared > 0:
        p["shared_gate"] = ("embed", "mlp")
        p["shared_up"] = ("embed", "mlp")
        p["shared_down"] = ("mlp", "embed")
    return p


def moe_ffn(
    x: Array,                  # (t, d) flattened tokens
    params: Dict[str, Array],
    cfg: MoEConfig,
) -> Tuple[Array, Array]:
    """Returns (output (t, d), aux_loss scalar)."""
    t, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    cap = cfg.capacity(t)
    compute_dtype = x.dtype

    e_pad = cfg.n_experts_padded

    # ---- routing ----------------------------------------------------------
    logits = (x.astype(jnp.float32) @ params["router"].astype(jnp.float32))
    if e_pad != e:  # mask pad experts: no tokens, no gradient
        logits = jnp.where(jnp.arange(e_pad) < e, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)                    # (t, e_pad)
    probs = probs[:, :e]
    gate, sel = jax.lax.top_k(probs, k)                        # (t, k)
    gate = gate / jnp.maximum(jnp.sum(gate, axis=-1, keepdims=True), 1e-9)

    # Switch-style load-balance aux loss
    density = jnp.mean(
        jax.nn.one_hot(sel[:, 0], e, dtype=jnp.float32), axis=0
    )
    density_proxy = jnp.mean(probs, axis=0)
    aux = cfg.router_aux_weight * e * jnp.sum(density * density_proxy)

    # ---- sort-based dispatch ------------------------------------------------
    # buffers are sized over the PADDED expert count so the expert axis of
    # every array matches the (possibly padded) expert weights; pad experts
    # receive no tokens (their buffer rows stay zero)
    flat_expert = sel.reshape(-1)                              # (t*k,)
    flat_token = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)
    flat_gate = gate.reshape(-1)
    order = jnp.argsort(flat_expert, stable=True)
    se = flat_expert[order]
    st = flat_token[order]
    sg = flat_gate[order]
    # position of each assignment within its expert segment
    counts = jnp.bincount(se, length=e_pad)                    # (e_pad,)
    seg_start = jnp.concatenate(
        [jnp.zeros((1,), counts.dtype), jnp.cumsum(counts)[:-1]]
    )
    pos = jnp.arange(t * k, dtype=jnp.int32) - jnp.take(seg_start, se).astype(jnp.int32)
    keep = pos < cap
    dest = jnp.where(keep, se * cap + pos, e_pad * cap)        # drop slot at end

    buf = jnp.zeros((e_pad * cap + 1, d), compute_dtype)
    buf = buf.at[dest].add(jnp.take(x, st, axis=0) * keep[:, None].astype(compute_dtype))
    buf = buf[:-1].reshape(e_pad, cap, d)

    # ---- batched expert FFN -------------------------------------------------
    g = jnp.einsum(
        "ecd,edf->ecf", buf, params["w_gate"].astype(compute_dtype),
        preferred_element_type=jnp.float32,
    )
    u = jnp.einsum(
        "ecd,edf->ecf", buf, params["w_up"].astype(compute_dtype),
        preferred_element_type=jnp.float32,
    )
    h = layers.swiglu(g, u).astype(compute_dtype)
    y = jnp.einsum(
        "ecf,efd->ecd", h, params["w_down"].astype(compute_dtype),
        preferred_element_type=jnp.float32,
    ).astype(compute_dtype)                                    # (e, cap, d)

    # ---- combine ------------------------------------------------------------
    y_flat = jnp.concatenate([y.reshape(e_pad * cap, d), jnp.zeros((1, d), y.dtype)])
    contrib = jnp.take(y_flat, dest, axis=0) * (
        sg * keep.astype(jnp.float32)
    )[:, None].astype(y.dtype)
    out = jnp.zeros((t, d), compute_dtype).at[st].add(contrib)

    # ---- shared experts ------------------------------------------------------
    if cfg.n_shared > 0:
        gs = x @ params["shared_gate"].astype(compute_dtype)
        us = x @ params["shared_up"].astype(compute_dtype)
        out = out + layers.swiglu(gs, us) @ params["shared_down"].astype(compute_dtype)

    return out, aux


# ---------------------------------------------------------------------------
# Expert-parallel dispatch via shard_map (the §Perf MoE hillclimb)
#
# Key insight: the token batch is sharded over the DATA axes and replicated
# over 'model', so expert parallelism needs NO token movement at all — each
# model shard routes its (replicated) local tokens, keeps only assignments
# to its own experts, runs them, and one psum of the (t_local, d) partial
# outputs over 'model' combines everything.  The GSPMD baseline instead
# scatters into a replicated (E, C, d) buffer and all-reduces ~16 GB per
# layer; this path all-reduces ~50 MB.
# ---------------------------------------------------------------------------


def moe_ffn_sharded(
    x: Array,                  # (t, d) flattened tokens, sharded over data
    params: Dict[str, Array],
    cfg: MoEConfig,
    mesh,
    model_axis: str = "model",
) -> Tuple[Array, Array]:
    """EP MoE: shard_map over the mesh, experts owned by 'model' shards.

    Requires cfg.n_experts_padded % mesh.shape[model_axis] == 0.
    Shared experts are NOT handled here (caller adds them; they are dense
    TP matmuls).  Returns (out (t, d), aux scalar).
    """
    from jax.sharding import PartitionSpec as P

    e, k = cfg.n_experts, cfg.top_k
    e_pad = cfg.n_experts_padded
    n_model = mesh.shape[model_axis]
    assert e_pad % n_model == 0, (e_pad, n_model)
    e_loc = e_pad // n_model
    data_axes = tuple(a for a in mesh.axis_names if a != model_axis)
    dspec = data_axes if len(data_axes) > 1 else (
        data_axes[0] if data_axes else None
    )
    compute_dtype = x.dtype

    def local_fn(x_loc, router, wg, wu, wd):
        t_loc, d = x_loc.shape
        m_idx = jax.lax.axis_index(model_axis)
        cap = cfg.capacity(t_loc)

        logits = x_loc.astype(jnp.float32) @ router.astype(jnp.float32)
        if e_pad != e:
            logits = jnp.where(jnp.arange(e_pad) < e, logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1)[:, :e]
        gate, sel = jax.lax.top_k(probs, k)
        gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

        density = jnp.mean(jax.nn.one_hot(sel[:, 0], e, dtype=jnp.float32), 0)
        density_proxy = jnp.mean(probs, axis=0)
        aux = cfg.router_aux_weight * e * jnp.sum(density * density_proxy)
        aux = jax.lax.pmean(aux, data_axes) if data_axes else aux

        flat_e = sel.reshape(-1)
        flat_t = jnp.repeat(jnp.arange(t_loc, dtype=jnp.int32), k)
        flat_g = gate.reshape(-1)
        order = jnp.argsort(flat_e, stable=True)
        se, st, sg = flat_e[order], flat_t[order], flat_g[order]
        counts = jnp.bincount(se, length=e_pad)
        seg_start = jnp.concatenate(
            [jnp.zeros((1,), counts.dtype), jnp.cumsum(counts)[:-1]]
        )
        pos = (
            jnp.arange(t_loc * k, dtype=jnp.int32)
            - jnp.take(seg_start, se).astype(jnp.int32)
        )
        own = (se >= m_idx * e_loc) & (se < (m_idx + 1) * e_loc)
        keep = own & (pos < cap)
        local_e = jnp.where(own, se - m_idx * e_loc, 0)
        dest = jnp.where(keep, local_e * cap + pos, e_loc * cap)

        buf = jnp.zeros((e_loc * cap + 1, d), compute_dtype)
        buf = buf.at[dest].add(
            jnp.take(x_loc, st, axis=0) * keep[:, None].astype(compute_dtype)
        )
        buf = buf[:-1].reshape(e_loc, cap, d)

        g = jnp.einsum(
            "ecd,edf->ecf", buf, wg.astype(compute_dtype),
            preferred_element_type=jnp.float32,
        )
        u = jnp.einsum(
            "ecd,edf->ecf", buf, wu.astype(compute_dtype),
            preferred_element_type=jnp.float32,
        )
        h = layers.swiglu(g, u).astype(compute_dtype)
        y = jnp.einsum(
            "ecf,efd->ecd", h, wd.astype(compute_dtype),
            preferred_element_type=jnp.float32,
        ).astype(compute_dtype)

        y_flat = jnp.concatenate(
            [y.reshape(e_loc * cap, d), jnp.zeros((1, d), y.dtype)]
        )
        contrib = jnp.take(y_flat, dest, axis=0) * (
            sg * keep.astype(jnp.float32)
        )[:, None].astype(y.dtype)
        out = jnp.zeros((t_loc, d), compute_dtype).at[st].add(contrib)
        # the ONLY cross-shard traffic: (t_loc, d) partial-output psum
        out = jax.lax.psum(out, model_axis)
        return out, aux

    out, aux = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(
            P(dspec, None),
            P(),                          # router replicated
            P(model_axis, None, None),    # expert weights EP-sharded
            P(model_axis, None, None),
            P(model_axis, None, None),
        ),
        out_specs=(P(dspec, None), P()),
        check_vma=False,
    )(x, params["router"], params["w_gate"], params["w_up"],
      params["w_down"])
    return out, aux
