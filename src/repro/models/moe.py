"""Mixture-of-Experts layer: top-k routing with GShard-style group-wise
capacity-bounded dispatch (TPU-idiomatic: one batched matmul per expert
weight, no per-token gather loops, no cross-shard sequential scans).

Dispatch (per group = per sequence, so ranking parallelizes over the
data-sharded batch axis):
  1. router logits -> top-k (expert_id, weight) per token;
  2. rank of each (token, k) assignment within its expert via a cumulative
     count over the group's token axis;
  3. scatter token activations into a dense (B, E, C, D) buffer (assignments
     whose rank exceeds capacity C are dropped — their weight is zeroed so
     the residual path carries those tokens, standard capacity semantics);
  4. batched expert FFN on the buffer;
  5. gather back + combine with routing weights.

Parallelism:
  * "tp": expert FFN hidden dim sharded on `model` (dense-MLP-like comms);
  * "ep": expert dim sharded on `model` — GSPMD materializes the token
    all-to-all when resharding the dispatch buffer batch->expert.

Serving (`moe_serve`: the paged verify and prefill programs, and the dense
slot backend's decode) is dropless by construction instead: the (token, expert) assignments are sorted by expert
and every routed expert runs as a grouped matmul over its contiguous rows
(`kernels/moe_gmm`), so the work grows with top-k and not with the expert
count, nothing is dropped, and a token's result does not depend on the
other tokens in the batch (DESIGN.md §2).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import MoEConfig
from repro.kernels.moe_gmm.ops import moe_gmm_op, padded_rows
from repro.models.layers import dense_param, _dense_init


def init_moe(rng, d_model, d_ff, cfg: MoEConfig, dtype):
    fe = cfg.d_expert or d_ff
    ks = jax.random.split(rng, 7)
    p = {
        "router": dense_param(ks[0], d_model, (cfg.num_experts,), jnp.float32),
        "w_gate": _dense_init(
            ks[1], (cfg.num_experts, d_model, fe), d_model, dtype
        ),
        "w_up": _dense_init(ks[2], (cfg.num_experts, d_model, fe), d_model, dtype),
        "w_down": _dense_init(ks[3], (cfg.num_experts, fe, d_model), fe, dtype),
    }
    if cfg.num_shared_experts:
        fs = fe * cfg.num_shared_experts
        p["shared"] = {
            "gate": dense_param(ks[4], d_model, (fs,), dtype),
            "up": dense_param(ks[5], d_model, (fs,), dtype),
            "down": _dense_init(ks[6], (fs, d_model), fs, dtype),
        }
    return p


def moe_axes(cfg: MoEConfig):
    if cfg.parallelism == "ep":
        w = ("expert", "embed", None)
        wd = ("expert", None, "embed")
    else:  # tp: shard the expert hidden dim like a dense MLP
        w = (None, "embed", "mlp")
        wd = (None, "mlp", "embed")
    a = {
        "router": ("embed", None),
        "w_gate": w,
        "w_up": w,
        "w_down": wd,
    }
    if cfg.num_shared_experts:
        a["shared"] = {
            "gate": ("embed", "mlp"),
            "up": ("embed", "mlp"),
            "down": ("mlp", "embed"),
        }
    return a


def _top_k(probs, cfg: MoEConfig):
    """The k largest routing probabilities and their experts, renormalized
    to sum to 1 only where the model does (``norm_topk_prob``)."""
    top_w, top_e = jax.lax.top_k(probs, cfg.top_k)
    if cfg.norm_topk_prob:
        top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)
    return top_w, top_e


def _shared(sp, x):
    """The always-on shared experts: one SwiGLU MLP of their summed width."""
    sg = jnp.einsum("...d,df->...f", x, sp["gate"])
    su = jnp.einsum("...d,df->...f", x, sp["up"])
    return jnp.einsum("...f,fd->...d", jax.nn.silu(sg) * su, sp["down"])


#: the routed experts' weights, which the paged layer scan hands the
#: grouped matmul stacked over layers (`moe_serve`'s ``experts``)
EXPERT_WEIGHTS = ("w_gate", "w_up", "w_down")


def moe_serve(p, x, cfg: MoEConfig, valid=None, *, experts=None, layer=None):
    """Dropless routed experts for serving: x (B, T, D) -> (B, T, D).

    Route: float32 router logits (``highest`` precision) and a softmax over
    all E experts, then top-k.  Dispatch: the B*T*k assignments sorted by
    expert (stable, so a token keeps its place within its expert), the
    token rows gathered in that order, and each expert's group size
    counted from the ids.  Experts: the grouped matmul of `moe_gmm_op`.
    Combine: the rows put back in token order and summed under their
    routing weights in float32, plus the shared experts.

    ``valid`` (B, T) marks real tokens.  The rest (draft padding, pad
    rows) route like any token, but their assignments sort after every
    expert's group, so the grouped matmul never computes them, and their
    routed part comes back 0.  Nothing of another token enters a token's
    result, so it is the same in any batch.

    ``experts`` (with ``layer``) gives the routed experts' weights stacked
    over layers, ``(L, E, ...)``, and the layer to use; without it they
    are ``p``'s own."""
    B, T, D = x.shape
    E, K = cfg.num_experts, cfg.top_k
    N, M = B * T, B * T * cfg.top_k
    xf = x.reshape(N, D)
    with jax.named_scope("moe_route"):
        logits = jnp.dot(xf.astype(jnp.float32), p["router"],
                         precision=jax.lax.Precision.HIGHEST)   # (N, E)
        top_w, top_e = _top_k(jax.nn.softmax(logits, axis=-1), cfg)
    with jax.named_scope("moe_dispatch"):
        expert = top_e.reshape(M)
        if valid is not None:
            expert = jnp.where(jnp.repeat(valid.reshape(N), K), expert, E)
        order = jnp.argsort(expert, stable=True)                # (M,)
        sizes = jnp.sum(expert[:, None] == jnp.arange(E), axis=0,
                        dtype=jnp.int32)                        # (E,)
        # pad rows to whole row tiles; they read token 0 and are not computed
        rows = jnp.pad(order // K, (0, padded_rows(M) - M))
        xs = jnp.take(xf, rows, axis=0)                         # (Mp, D)
    with jax.named_scope("moe_experts"):
        if experts is None:
            experts, layer = tuple(p[k] for k in EXPERT_WEIGHTS), None
        ys = moe_gmm_op(xs, *experts, sizes, layer)
    with jax.named_scope("moe_combine"):
        slot = jnp.zeros(M, jnp.int32).at[order].set(
            jnp.arange(M, dtype=jnp.int32))                     # unsort
        yk = jnp.take(ys, slot, axis=0).reshape(N, K, D)
        y = (top_w[..., None] * yk).sum(axis=1)                 # f32
        if valid is not None:
            y = jnp.where(valid.reshape(N, 1), y, 0.0)
        y = y.astype(x.dtype)
        if cfg.num_shared_experts:
            y = y + _shared(p["shared"], xf)
    return y.reshape(B, T, D)


def expert_capacity(cfg: MoEConfig, tokens_per_group: int) -> int:
    C = int(round(tokens_per_group * cfg.top_k / cfg.num_experts * cfg.capacity_factor))
    return max(cfg.top_k, min(C, tokens_per_group))


def moe_apply(p, x, cfg: MoEConfig, *, ctx=None, rng=None):
    """x: (B, S, D) -> ((B, S, D), aux losses).  Groups = batch rows, with
    GShard capacity (training and the dense slot backend's prefill).
    Verification and the paged serving programs use `moe_serve` instead.
    """
    B, S, D = x.shape
    E, K = cfg.num_experts, cfg.top_k

    logits = jnp.einsum(
        "bsd,de->bse", x.astype(jnp.float32), p["router"]
    )  # (B, S, E) f32
    if cfg.router_jitter and rng is not None:
        logits = logits + cfg.router_jitter * jax.random.normal(rng, logits.shape)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = _top_k(probs, cfg)                           # (B, S, K)

    C = expert_capacity(cfg, S)

    # rank within (group, expert): cumulative count along the S*K axis
    flat_e = top_e.reshape(B, S * K)                            # (B, S*K)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)         # (B, S*K, E)
    ranks = (jnp.cumsum(onehot, axis=1) - onehot) * onehot
    rank = ranks.sum(-1)                                        # (B, S*K)
    keep = rank < C
    slot = flat_e * C + jnp.minimum(rank, C - 1)                # (B, S*K)
    oob = E * C                                                  # drop sentinel

    # scatter tokens into (B, E*C, D)
    src = jnp.repeat(x, K, axis=1)                              # (B, S*K, D)
    buf = jnp.zeros((B, E * C, D), x.dtype)
    scatter_idx = jnp.where(keep, slot, oob)[..., None]         # (B, S*K, 1)
    buf = jax.vmap(
        lambda b, i, s: b.at[i[..., 0]].add(s, mode="drop")
    )(buf, scatter_idx, src)
    buf = buf.reshape(B, E, C, D)
    if ctx is not None:
        buf = ctx.cs(buf, ("act_batch", "act_expert", None, None))

    # expert FFN (batched over E; groups stay data-sharded)
    g = jnp.einsum("becd,edf->becf", buf, p["w_gate"])
    u = jnp.einsum("becd,edf->becf", buf, p["w_up"])
    h = jax.nn.silu(g) * u
    if ctx is not None and cfg.parallelism == "tp":
        h = ctx.cs(h, ("act_batch", None, None, "mlp"))
    out_buf = jnp.einsum("becf,efd->becd", h, p["w_down"])
    if ctx is not None:
        out_buf = ctx.cs(out_buf, ("act_batch", "act_expert", None, None))
    out_buf = out_buf.reshape(B, E * C, D)

    # gather back, apply routing weights (dropped tokens contribute 0)
    gathered = jnp.take_along_axis(
        out_buf, jnp.minimum(slot, E * C - 1)[..., None], axis=1
    )                                                           # (B, S*K, D)
    w = (top_w.reshape(B, S * K) * keep.astype(jnp.float32)).astype(x.dtype)
    y = (gathered * w[..., None]).reshape(B, S, K, D).sum(axis=2)

    if cfg.num_shared_experts:
        y = y + _shared(p["shared"], x)

    # load-balance aux loss (Switch-style)
    me = probs.reshape(-1, E).mean(axis=0)
    fe_frac = jax.nn.one_hot(
        top_e[..., 0].reshape(-1), E, dtype=jnp.float32
    ).mean(axis=0)
    aux = {"load_balance": E * jnp.sum(me * fe_frac)}
    return y, aux
