"""Gated MLP and Mixture-of-Experts layers.

The MoE uses capacity-based top-k routing with an explicit
``jax.shard_map`` dispatch: tokens
are routed *locally per data shard*
(scatter into an (E, C, d) buffer), expert FFNs run with d_ff
tensor-parallel over the 'model' axis, and the partial outputs are
``psum``-combined. This keeps compiled FLOPs proportional to *active*
parameters (honest MoE roofline) while avoiding the (N, E, C) one-hot
dispatch einsum whose memory explodes at 32k sequence lengths.

Expert-parallel sharding rule (divisibility-aware, see DESIGN.md):
d_ff is sharded over 'model' whenever divisible (all three assigned MoE
archs: grok 32768/16, granite 512/16, jamba 24576/16); otherwise the
expert weights are replicated and the psum is skipped.
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.models.common import AxisSizes, KeyGen, normal_init, shard

CAPACITY_FACTOR = 1.25


def init_dense_mlp(kg: KeyGen, cfg: ArchConfig, dtype=jnp.float32) -> Dict:
    d, f = cfg.d_model, cfg.d_ff
    return {
        "w1": normal_init(kg(), (d, f), d ** -0.5, dtype),
        "w3": normal_init(kg(), (d, f), d ** -0.5, dtype),
        "w2": normal_init(kg(), (f, d), f ** -0.5, dtype),
    }


def dense_mlp_specs(cfg: ArchConfig, ax: AxisSizes) -> Dict:
    d, f = cfg.d_model, cfg.d_ff
    return {
        "w1": ax.spec(("data", "model"), (d, f)),
        "w3": ax.spec(("data", "model"), (d, f)),
        "w2": ax.spec(("model", "data"), (f, d)),
    }


def dense_mlp(p: Dict, x: jax.Array, ax: AxisSizes) -> jax.Array:
    h = jax.nn.silu(x @ p["w1"]) * (x @ p["w3"])
    h = shard(h, ax, (ax.batch_axes, None, "model"))
    return h @ p["w2"]


# ----------------------------------------------------------------------- MoE

def init_moe(kg: KeyGen, cfg: ArchConfig, dtype=jnp.float32) -> Dict:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    return {
        "router": normal_init(kg(), (d, e), d ** -0.5, jnp.float32),
        "w1": normal_init(kg(), (e, d, f), d ** -0.5, dtype),
        "w3": normal_init(kg(), (e, d, f), d ** -0.5, dtype),
        "w2": normal_init(kg(), (e, f, d), f ** -0.5, dtype),
    }


def moe_specs(cfg: ArchConfig, ax: AxisSizes) -> Dict:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    return {
        "router": P(None, None),
        "w1": ax.spec((None, "data", "model"), (e, d, f)),
        "w3": ax.spec((None, "data", "model"), (e, d, f)),
        "w2": ax.spec((None, "model", "data"), (e, f, d)),
    }


def _capacity(n_local: int, cfg: ArchConfig) -> int:
    c = int(cfg.experts_per_token * n_local * CAPACITY_FACTOR
            / cfg.n_experts) + 1
    return max(8, -(-c // 8) * 8)   # round up to a multiple of 8


def _moe_local(xl: jax.Array, router: jax.Array, w1: jax.Array,
               w3: jax.Array, w2: jax.Array, cfg: ArchConfig,
               model_sharded: bool) -> jax.Array:
    """Per-data-shard MoE: local dispatch, TP expert FFN, psum combine."""
    nl, d = xl.shape
    e, k = cfg.n_experts, cfg.experts_per_token
    cap = _capacity(nl, cfg)
    logits = xl.astype(jnp.float32) @ router                 # (nl, e)
    probs = jax.nn.softmax(logits, axis=-1)
    pk, ik = jax.lax.top_k(probs, k)                         # (nl, k)
    pk = (pk / jnp.sum(pk, -1, keepdims=True)).astype(xl.dtype)
    # Slot assignment: position of each (token, choice) within its expert.
    onehot = jax.nn.one_hot(ik.reshape(-1), e, dtype=jnp.int32)  # (nl*k, e)
    slot = (jnp.cumsum(onehot, axis=0) * onehot).sum(-1) - 1     # 0-based
    slot = slot.reshape(nl, k)
    keep = slot < cap                                         # capacity drop
    # Dispatch: scatter tokens into the (e, cap, d) expert buffer.
    buf = jnp.zeros((e, cap, d), xl.dtype)
    buf = buf.at[ik, slot].add(
        jnp.where(keep[..., None], xl[:, None, :], 0), mode="drop")
    # Expert FFN (d_ff tensor-parallel over 'model' when sharded).
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, w1)) \
        * jnp.einsum("ecd,edf->ecf", buf, w3)
    out_e = jnp.einsum("ecf,efd->ecd", h, w2)
    if model_sharded:
        # Combine in the compute dtype (bf16 on TPU): halves the TP psum
        # wire bytes vs fp32 at no accuracy cost (expert FFN ran in bf16
        # anyway; the router weights are applied after the psum).
        out_e = jax.lax.psum(out_e.astype(xl.dtype), "model")
    # Combine: gather back and weight by (renormalized) router probs.
    gathered = out_e.at[ik, slot].get(mode="fill", fill_value=0)  # (nl,k,d)
    gathered = jnp.where(keep[..., None], gathered, 0)
    return jnp.sum(gathered * pk[..., None], axis=1)


def moe_mlp(p: Dict, x: jax.Array, cfg: ArchConfig, ax: AxisSizes,
            mesh) -> jax.Array:
    """x: (B, S, d) → (B, S, d)."""
    b, s, d = x.shape
    xf = x.reshape(b * s, d)
    f_sharded = cfg.d_ff % ax.size("model") == 0 and ax.size("model") > 1
    # Tokens shard over the batch axes when divisible (train/prefill);
    # small decode batches replicate (the FFN is tiny at N=1 anyway).
    batch = ax.batch_axes if (b * s) % ax.size(ax.batch_axes) == 0 else None
    in_specs = (
        P(batch, None),                                    # tokens
        P(None, None),                                     # router
        P(None, None, "model") if f_sharded else P(None, None, None),
        P(None, None, "model") if f_sharded else P(None, None, None),
        P(None, "model", None) if f_sharded else P(None, None, None),
    )
    fn = functools.partial(_moe_local, cfg=cfg, model_sharded=f_sharded)
    out = jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=P(batch, None),
        check_vma=False,
    )(xf, p["router"], p["w1"], p["w3"], p["w2"])
    return out.reshape(b, s, d)
