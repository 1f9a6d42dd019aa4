"""Mixture-of-Experts layer with sort-based (owner-computes) dispatch.

The dispatch is deliberately the same bucket-packing used by the BFS queue
exchange (core/frontier.build_queue_buckets): tokens are "candidate
vertices", the expert index is the "owner", and capacity plays the role of
the send-buffer cap.  Sorting assignments by expert and scattering into an
(E, C, D) buffer keeps HLO FLOPs proportional to real expert compute —
unlike the GShard one-hot einsum dispatch, whose (T, E, C) tensors add
O(T^2) fake FLOPs that would pollute the roofline's compute term
(EXPERIMENTS.md §Perf discusses this choice).

Under pjit the buffer is sharded over the expert axis, so the scatter
becomes the token all-to-all of expert parallelism — the direct exchange
of paper §5.1-2 applied to tokens instead of vertices.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import MoEConfig
from repro.layers.core import swiglu
from repro.models import sharding_hints as hints


def init_moe_params(key, d_model: int, cfg: MoEConfig, dtype):
    ks = jax.random.split(key, 5)
    e, f = cfg.n_experts, cfg.d_ff
    scale_in = d_model ** -0.5
    p = {
        "router": jax.random.normal(ks[0], (d_model, e), jnp.float32) * scale_in,
        "w_gate": (jax.random.normal(ks[1], (e, d_model, f)) * scale_in).astype(dtype),
        "w_up": (jax.random.normal(ks[2], (e, d_model, f)) * scale_in).astype(dtype),
        "w_down": (jax.random.normal(ks[3], (e, f, d_model)) * f ** -0.5).astype(dtype),
    }
    if cfg.shared_experts:
        fs = cfg.d_ff * cfg.shared_experts
        p["shared"] = {
            "w_gate": (jax.random.normal(ks[4], (d_model, fs)) * scale_in).astype(dtype),
            "w_up": (jax.random.normal(jax.random.fold_in(ks[4], 1),
                                       (d_model, fs)) * scale_in).astype(dtype),
            "w_down": (jax.random.normal(jax.random.fold_in(ks[4], 2),
                                         (fs, d_model)) * fs ** -0.5).astype(dtype),
        }
    return p


def capacity(tokens: int, cfg: MoEConfig) -> int:
    c = int(tokens * cfg.top_k * cfg.capacity_factor / cfg.n_experts)
    return max(8, -(-c // 8) * 8)


def moe_apply(params, x: jnp.ndarray, cfg: MoEConfig):
    """x: (T, D) -> (out, aux). Dispatches to the expert-parallel shard_map
    implementation when launcher sharding hints are active."""
    if hints.enabled():
        return moe_apply_sharded(params, x, cfg)
    return _moe_apply_local(params, x, cfg)


def _moe_apply_local(params, x: jnp.ndarray, cfg: MoEConfig):
    """Single-shard reference path (smoke tests, examples)."""
    t, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    c = capacity(t, cfg)

    logits = x.astype(jnp.float32) @ params["router"]          # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, k)            # (T, K)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    # --- bucket-pack assignments by expert (cf. BFS queue exchange) ---
    slot_expert = expert_idx.reshape(-1)                       # (T*K,)
    slot_token = jnp.repeat(jnp.arange(t), k)
    slot_gate = gate_vals.reshape(-1)
    order = jnp.argsort(slot_expert)                           # stable
    se, stok, sg = slot_expert[order], slot_token[order], slot_gate[order]
    starts = jnp.searchsorted(se, jnp.arange(e + 1))
    rank = jnp.arange(t * k) - starts[se]
    keep = rank < c
    slot = jnp.where(keep, se * c + rank, e * c)               # drop -> pad row

    buf = jnp.zeros((e * c + 1, d), x.dtype).at[slot].set(x[stok])
    expert_in = hints.constrain_expert_buffer(buf[:-1].reshape(e, c, d))

    # --- per-expert SwiGLU (batched einsum over the expert dim) ---
    h = jnp.einsum("ecd,edf->ecf", expert_in, params["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", expert_in, params["w_up"])
    expert_out = jnp.einsum("ecf,efd->ecd", jax.nn.silu(h) * u,
                            params["w_down"])                  # (E, C, D)
    expert_out = hints.constrain_expert_buffer(expert_out)

    # --- combine: gather back and weight by gate ---
    flat_out = expert_out.reshape(e * c, d)
    slot_safe = jnp.minimum(slot, e * c - 1)
    contrib = flat_out[slot_safe] * (sg * keep)[:, None].astype(x.dtype)
    out = jax.ops.segment_sum(contrib, stok, num_segments=t)

    if cfg.shared_experts:
        sp = params["shared"]
        out = out + swiglu(x, sp["w_gate"], sp["w_up"], sp["w_down"])

    # Switch-style load-balance aux loss (fraction * mean prob per expert).
    frac = jnp.mean(jax.nn.one_hot(expert_idx[:, 0], e, dtype=jnp.float32), 0)
    mean_p = probs.mean(0)
    aux = {"lb_loss": e * jnp.sum(frac * mean_p),
           "dropped": (~keep).sum()}
    return out.astype(x.dtype), aux


# ---------------------------------------------------------------------------
# Expert-parallel shard_map path (production): tokens sharded over the data
# axes, experts sharded over the model axis.  Each device routes its local
# tokens, runs only the experts it owns, and partial outputs are summed over
# the model axis — the owner-computes rule of the paper applied to experts.
# Dispatch buffers are per-shard (E_local, C_local, D), so nothing scales
# with the global token count on any one chip.
# ---------------------------------------------------------------------------

def _moe_local_experts(params_local, x_local, cfg: MoEConfig, e_local: int,
                       model_axis, dp_axes):
    """Runs on one shard: params_local holds this shard's expert slices."""
    import jax
    from jax import lax

    t_loc, d = x_local.shape
    e, k = cfg.n_experts, cfg.top_k
    c = capacity(t_loc, cfg)

    logits = x_local.astype(jnp.float32) @ params_local["router"]  # (Tl, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, k)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    my_e0 = lax.axis_index(model_axis) * e_local
    slot_expert = expert_idx.reshape(-1)
    slot_token = jnp.repeat(jnp.arange(t_loc), k)
    slot_gate = gate_vals.reshape(-1)
    local_e = slot_expert - my_e0
    mine = (local_e >= 0) & (local_e < e_local)
    owner = jnp.where(mine, local_e, e_local)              # sentinel bucket

    order = jnp.argsort(owner)
    se, stok, sg = owner[order], slot_token[order], slot_gate[order]
    starts = jnp.searchsorted(se, jnp.arange(e_local + 1))
    rank = jnp.arange(t_loc * k) - starts[jnp.minimum(se, e_local)]
    keep = (se < e_local) & (rank < c)
    slot = jnp.where(keep, se * c + rank, e_local * c)

    # Index-based dispatch: scatter token *ids* into the buffer slots, then
    # gather features straight into (E_local, C, D).  Never materializes a
    # (T*K, D) duplicate-token tensor (the 6 GiB/buffer offender the value-
    # scatter version produced; EXPERIMENTS.md §Perf).
    buf_tok = jnp.full((e_local * c + 1,), t_loc, jnp.int32).at[slot].set(
        stok.astype(jnp.int32))[:-1]
    buf_gate = jnp.zeros((e_local * c + 1,), jnp.float32).at[slot].set(
        sg * keep)[:-1]
    x_pad = jnp.concatenate([x_local, jnp.zeros((1, d), x_local.dtype)], 0)
    expert_in = x_pad[buf_tok].reshape(e_local, c, d)

    h = jnp.einsum("ecd,edf->ecf", expert_in, params_local["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", expert_in, params_local["w_up"])
    expert_out = jnp.einsum("ecf,efd->ecd", jax.nn.silu(h) * u,
                            params_local["w_down"])

    flat_out = expert_out.reshape(e_local * c, d)
    contrib = flat_out * buf_gate[:, None].astype(flat_out.dtype)
    partial = jnp.zeros((t_loc + 1, d), jnp.float32).at[buf_tok].add(
        contrib.astype(jnp.float32))[:t_loc]
    # owner-computes merge: sum expert partials over the model axis
    out = lax.psum(partial, model_axis).astype(x_local.dtype)

    if cfg.shared_experts:
        sp = params_local["shared"]
        out = out + swiglu(x_local, sp["w_gate"], sp["w_up"], sp["w_down"])

    frac = jnp.mean(jax.nn.one_hot(expert_idx[:, 0], e, dtype=jnp.float32), 0)
    mean_p = probs.mean(0)
    lb = e * jnp.sum(frac * mean_p)
    lb = lax.pmean(lb, dp_axes)
    dropped = lax.psum((~keep).sum() - (~mine).sum(), (*dp_axes, model_axis))
    return out, lb, dropped


def moe_apply_sharded(params, x: jnp.ndarray, cfg: MoEConfig):
    import functools
    import jax
    from jax.sharding import PartitionSpec as P

    st = hints._STATE
    mesh, dp, model = st["mesh"], st["dp"], st["model"]
    e = cfg.n_experts
    msize = mesh.shape[model]
    if e % msize != 0 or x.shape[0] % int(
            __import__("numpy").prod([mesh.shape[a] for a in dp])) != 0:
        return _moe_apply_local(params, x, cfg)
    e_local = e // msize

    pspecs = {"router": P(None, None),
              "w_gate": P(model, None, None),
              "w_up": P(model, None, None),
              "w_down": P(model, None, None)}
    if cfg.shared_experts:
        pspecs["shared"] = {"w_gate": P(None, None), "w_up": P(None, None),
                            "w_down": P(None, None)}
    fn = functools.partial(_moe_local_experts, cfg=cfg, e_local=e_local,
                           model_axis=model, dp_axes=dp)
    out, lb, dropped = jax.shard_map(
        fn, mesh=mesh,
        in_specs=(pspecs, P(dp, None)),
        out_specs=(P(dp, None), P(), P()),
        check_vma=False,
    )(params, x)
    return out, {"lb_loss": lb, "dropped": dropped}
