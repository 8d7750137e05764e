"""The plain reference: the published forward pass in float32.

Straight ``jax.numpy`` at ``highest`` matmul precision, one sequence at a
time and one layer at a time, with no kernel, cache, paging or batching.
It follows the configuration file (Hugging Face ``config.json`` keys):
RMSNorm, rotary embeddings on half-split dims, grouped-query causal
softmax attention with optional q/k/v bias, and either a SwiGLU MLP or a
DeepSeekMoE layer (softmax router over the routed experts, the top-k
mixed, with the shared experts' MLP added; every routed expert is
computed for every token and masked by the routing weights, so nothing
is ever dropped).  It imports nothing of the program: its weights are
drawn again from the seed by `bench.weights.layer_leaf`.

``quant="int8"`` is the control: the same forward with every linear
layer's weights quantized per output channel and its inputs per token,
symmetric int8 (W8A8), the step below the bfloat16 the configuration
serves in.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W

BLOCK_Q = 512        # attention query rows per block
HEAD_ROWS = 256      # head rows per block


def dims(c: dict) -> dict:
    D, H = c["hidden_size"], c["num_attention_heads"]
    return dict(D=D, H=H, Hkv=c["num_key_value_heads"],
                hd=c.get("head_dim") or D // H, V=c["vocab_size"],
                L=c["num_hidden_layers"], eps=c["rms_norm_eps"],
                theta=float(c["rope_theta"]))


def layout(c: dict) -> dict:
    """Served parameter layout: ``path -> (per-layer shape, dtype,
    stacked)``.  Every leaf is served in bfloat16 except the MoE router,
    which the router's float32 logits need."""
    d = dims(c)
    D, H, Hkv, hd, V = d["D"], d["H"], d["Hkv"], d["hd"], d["V"]
    bf = jnp.bfloat16
    out = {
        "embed": ((V, D), bf, False),
        "final_norm": ((D,), bf, False),
        "unembed": ((D, V), bf, False),
        "blocks/ln1": ((D,), bf, True),
        "blocks/ln2": ((D,), bf, True),
        "blocks/attn/wq": ((D, H, hd), bf, True),
        "blocks/attn/wk": ((D, Hkv, hd), bf, True),
        "blocks/attn/wv": ((D, Hkv, hd), bf, True),
        "blocks/attn/wo": ((H, hd, D), bf, True),
    }
    if c.get("attention_bias"):
        out["blocks/attn/bq"] = ((H, hd), bf, True)
        out["blocks/attn/bk"] = ((Hkv, hd), bf, True)
        out["blocks/attn/bv"] = ((Hkv, hd), bf, True)
    if c.get("n_routed_experts"):
        E, fe = c["n_routed_experts"], c["moe_intermediate_size"]
        fs = fe * c.get("n_shared_experts", 0)
        out.update({
            "blocks/moe/router": ((D, E), jnp.float32, True),
            "blocks/moe/w_gate": ((E, D, fe), bf, True),
            "blocks/moe/w_up": ((E, D, fe), bf, True),
            "blocks/moe/w_down": ((E, fe, D), bf, True),
        })
        if fs:
            out.update({
                "blocks/moe/shared/gate": ((D, fs), bf, True),
                "blocks/moe/shared/up": ((D, fs), bf, True),
                "blocks/moe/shared/down": ((fs, D), bf, True),
            })
    else:
        F = c["intermediate_size"]
        out.update({
            "blocks/mlp/gate": ((D, F), bf, True),
            "blocks/mlp/up": ((D, F), bf, True),
            "blocks/mlp/down": ((F, D), bf, True),
        })
    return out


def check_layout(c: dict, program_tree_spec) -> None:
    """Raise unless the program serves exactly the layout above (each
    stacked leaf with the layer count in front)."""
    want = layout(c)
    got = {p: (s.shape, s.dtype, st)
           for p, s, st in W.leaf_specs(program_tree_spec)}
    L = dims(c)["L"]
    if set(got) != set(want):
        raise ValueError(f"program parameters {sorted(set(got) ^ set(want))}"
                         " differ from the configuration's layout")
    for p, (shape, dt, st) in want.items():
        full = ((L,) if st else ()) + tuple(shape)
        if tuple(got[p][0]) != full or jnp.dtype(got[p][1]) != jnp.dtype(dt):
            raise ValueError(f"{p}: program {got[p][:2]} != {full} {dt}")


def layer_weights(c: dict, seed: int, layer: int, quant: str | None):
    """One layer's weights in float32 (int8-rounded for the control)."""
    out = {}
    for p, (shape, dt, st) in layout(c).items():
        if not p.startswith("blocks/"):
            continue
        w = W.layer_leaf(seed, p, layer, shape, dt).astype(jnp.float32)
        name = p.split("/")[-1]
        if quant == "int8" and name in _OUT_AXES:
            w = _quant_w(w, _OUT_AXES[name])
        out[p[len("blocks/"):]] = w
    return out


def top_weights(c: dict, seed: int, quant: str | None):
    out = {}
    for p, (shape, dt, st) in layout(c).items():
        if p.startswith("blocks/"):
            continue
        w = W.layer_leaf(seed, p, 0, shape, dt).astype(jnp.float32)
        if quant == "int8" and p in ("embed", "unembed"):
            w = _quant_w(w, (0,) if p == "embed" else (1,))
        out[p] = w
    return out


# -- int8 fake quantization (the control) --------------------------------
#: per leaf, the axes that index an output channel (the scale is shared
#: over the other, contracted axes)
_OUT_AXES = {"wq": (1, 2), "wk": (1, 2), "wv": (1, 2), "wo": (2,),
             "gate": (1,), "up": (1,), "down": (1,),
             "w_gate": (0, 2), "w_up": (0, 2), "w_down": (0, 2)}


@functools.partial(jax.jit, static_argnums=(1,))
def _quant_w(w, out_axes):
    red = tuple(i for i in range(w.ndim) if i not in out_axes)
    s = jnp.max(jnp.abs(w), axis=red, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.round(w / s).clip(-127, 127) * s


def _quant_a(x, on: bool):
    if not on:
        return x
    s = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.round(x / s).clip(-127, 127) * s


# -- the forward ----------------------------------------------------------
def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None, None] * inv
    x1, x2 = x[..., :half], x[..., half:]
    c, s = jnp.cos(ang), jnp.sin(ang)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


@functools.partial(jax.jit, static_argnames=("d", "moe", "q8"))
def _layer(x, w, *, d, moe, q8):
    """One decoder layer over a whole (padded) sequence ``x`` (T, D)."""
    with jax.default_matmul_precision("highest"):
        T = x.shape[0]
        pos = jnp.arange(T)
        a = w["attn"]
        h = _quant_a(_rms(x, w["ln1"], d["eps"]), q8)
        q = jnp.einsum("td,dhk->thk", h, a["wq"])
        k = jnp.einsum("td,dhk->thk", h, a["wk"])
        v = jnp.einsum("td,dhk->thk", h, a["wv"])
        if "bq" in a:
            q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
        q, k = _rope(q, pos, d["theta"]), _rope(k, pos, d["theta"])
        G = d["H"] // d["Hkv"]
        k = jnp.repeat(k, G, axis=1)
        v = jnp.repeat(v, G, axis=1)
        outs = []
        for s0 in range(0, T, BLOCK_Q):
            qb = q[s0:s0 + BLOCK_Q]
            sc = jnp.einsum("qhk,thk->hqt", qb, k) * d["hd"] ** -0.5
            causal = (s0 + jnp.arange(qb.shape[0]))[:, None] >= pos[None, :]
            sc = jnp.where(causal[None], sc, -jnp.inf)
            outs.append(jnp.einsum("hqt,thk->qhk", jax.nn.softmax(sc, -1), v))
        o = _quant_a(jnp.concatenate(outs, 0), q8)
        x = x + jnp.einsum("thk,hkd->td", o, a["wo"])
        h = _rms(x, w["ln2"], d["eps"])
        if moe is None:
            m = w["mlp"]
            hq = _quant_a(h, q8)
            f = jax.nn.silu(hq @ m["gate"]) * (hq @ m["up"])
            return x + _quant_a(f, q8) @ m["down"]
        E, top_k, renorm = moe
        m = w["moe"]
        probs = jax.nn.softmax(h @ m["router"], -1)             # (T, E)
        top_w, top_e = jax.lax.top_k(probs, top_k)
        if renorm:
            top_w = top_w / top_w.sum(-1, keepdims=True)
        gate_w = jnp.zeros_like(probs).at[
            jnp.arange(T)[:, None], top_e].set(top_w)           # (T, E)
        hq = _quant_a(h, q8)

        def expert(acc, e):
            f = jax.nn.silu(hq @ m["w_gate"][e]) * (hq @ m["w_up"][e])
            return acc + gate_w[:, e][:, None] * (_quant_a(f, q8)
                                               @ m["w_down"][e]), None

        y, _ = jax.lax.scan(expert, jnp.zeros_like(x), jnp.arange(E))
        if "shared" in m:
            s = m["shared"]
            f = jax.nn.silu(hq @ s["gate"]) * (hq @ s["up"])
            y = y + _quant_a(f, q8) @ s["down"]
        return x + y


def _nest(flat: dict) -> dict:
    out: dict = {}
    for p, v in flat.items():
        node = out
        *head, last = p.split("/")
        for k in head:
            node = node.setdefault(k, {})
        node[last] = v
    return out


def _pad_len(n: int) -> int:
    return -(-n // BLOCK_Q) * BLOCK_Q


def hidden_states(c: dict, seed: int, seqs: list, quant: str | None = None,
                  pad_to: int = 0):
    """Final hidden states (before the last norm) of each token sequence
    in ``seqs``, layer by layer over all of them: a list of (T_i, D)
    float32 device arrays.  Every sequence is padded to one length (at
    least ``pad_to``), so one program serves all; pad tokens after a
    sequence's end do not reach its positions (causal attention)."""
    d = dims(c)
    q8 = quant == "int8"
    moe = None
    if c.get("n_routed_experts"):
        moe = (c["n_routed_experts"], c["num_experts_per_tok"],
               bool(c.get("norm_topk_prob")))
    top = top_weights(c, seed, quant)
    xs = []
    T = _pad_len(max([pad_to] + [len(s) for s in seqs]))
    for s in seqs:
        t = np.zeros(T, np.int32)
        t[:len(s)] = s
        xs.append(jnp.take(top["embed"], jnp.asarray(t), axis=0))
    del top
    for layer in range(d["L"]):
        w = _nest(layer_weights(c, seed, layer, quant))
        xs = [_layer(x, w, d=_hd(d), moe=moe, q8=q8) for x in xs]
        del w
    return [x[:len(s)] for x, s in zip(xs, seqs)]


def _hd(d: dict):
    """Hashable dims for the jit's static argument."""
    return _Frozen(d)


class _Frozen(dict):
    def __hash__(self):
        return hash(tuple(sorted(self.items())))


#: bisection steps and the largest shift (nats) `_shift` looks for
SHIFT_STEPS, SHIFT_MAX = 40, 32.0


def _shift(logp, g, qv, tok):
    """Per row: the least ``d >= 0`` (nats) such that raising the log-prob
    of ``tok`` by ``d`` and lowering every other token's by ``d`` makes
    ``tok`` the argmax of ``log(max(p - qv, 0)) + g``: how far the
    reference's log-probs are from deciding that draw as ``tok``.  A
    served path whose log-probs lie within ``d`` of the reference's at
    every token reads at most ``d``, however close ``p`` lies to ``q``
    (where the score's own gap is unbounded).  ``SHIFT_MAX`` when no shift
    up to it does."""
    onehot = jnp.arange(logp.shape[1])[None, :] == tok[:, None]
    sign = jnp.where(onehot, 1.0, -1.0)

    def wins(d):
        r = jnp.maximum(jnp.exp(logp + sign * d[:, None]) - qv[:, None], 0.0)
        s = jnp.log(jnp.maximum(r, 1e-38)) + g
        s_tok = jnp.take_along_axis(s, tok[:, None], -1)[:, 0]
        return s_tok >= jnp.max(jnp.where(onehot, -jnp.inf, s), -1)

    def step(_, lohi):
        lo, hi = lohi
        mid = 0.5 * (lo + hi)
        w = wins(mid)
        return jnp.where(w, lo, mid), jnp.where(w, mid, hi)

    z = jnp.zeros(logp.shape[0], jnp.float32)
    lo, hi = jax.lax.fori_loop(0, SHIFT_STEPS, step, (z, z + SHIFT_MAX))
    return jnp.where(wins(z), 0.0, hi)


@functools.partial(jax.jit, static_argnames=("eps", "q8"))
def _row_stats(h, norm, unembed, x_tok, mode, keys, probe, qhat, *, eps,
               q8):
    """Per row of hidden states: the log-prob of the row's draft token
    ``x_tok``, and for rows that choose a token (``mode`` 1: residual
    correction, 2: bonus sample, 3: greedy first token) the choice score
    ``z`` -- the log residual (or log p) plus the row's Gumbel noise, or the
    raw logit for greedy -- as its maximum, its argmax and its value at
    each ``probe`` token; at each probe token of a sampling row also its
    log-prob and its `_shift`."""
    with jax.default_matmul_precision("highest"):
        lg = _quant_a(_rms(h, norm, eps), q8) @ unembed      # (R, V)
        logp = jax.nn.log_softmax(lg, -1)
        lp_x = jnp.take_along_axis(logp, jnp.maximum(x_tok, 0)[:, None],
                                   -1)[:, 0]
        p = jnp.exp(logp)
        qv = jnp.where(mode == 1, qhat, 0.0)
        resid = jnp.maximum(p - qv[:, None], 0.0)
        g = jax.vmap(lambda k: jax.random.gumbel(
            k, (lg.shape[1],), jnp.float32))(keys)
        z = jnp.where((mode == 3)[:, None], lg,
                      jnp.log(jnp.maximum(resid, 1e-38)) + g)
        pr = jnp.maximum(probe, 0)
        zprobe = jnp.take_along_axis(z, pr, -1)
        lp_probe = jnp.take_along_axis(logp, pr, -1)
        sampled = (mode == 1) | (mode == 2)
        shift = jnp.stack(
            [jnp.where(sampled & (probe[:, j] >= 0),
                       _shift(logp, g, qv, pr[:, j]), 0.0)
             for j in range(probe.shape[1])], -1)
        return (lp_x, z.max(-1), jnp.argmax(z, -1).astype(jnp.int32), zprobe,
                lp_probe, shift)


def row_stats(c: dict, seed: int, hidden: list, rows: list,
              quant: str | None = None) -> dict:
    """`_row_stats` over the listed rows of each sequence's hidden states.

    ``rows[i]`` is a dict of equal-length arrays for sequence ``i``:
    ``pos`` (row index), ``x_tok``, ``mode``, ``key`` (n, 2) uint32 and
    ``probe`` (n, P) int32 (-1 for none).  Returns the six outputs,
    concatenated over sequences in order."""
    d = dims(c)
    top = top_weights(c, seed, quant)
    norm, unembed = top["final_norm"], top["unembed"]
    qhat = jnp.float32(1.0 / d["V"])
    outs = {"lp_x": [], "zmax": [], "zarg": [], "zprobe": [],
            "lp_probe": [], "shift": []}
    for h, r in zip(hidden, rows):
        n = len(r["pos"])
        for s0 in range(0, n, HEAD_ROWS):
            sl = slice(s0, s0 + HEAD_ROWS)
            m = len(r["pos"][sl])
            pad = lambda a, fill: np.concatenate(
                [a[sl], np.full((HEAD_ROWS - m,) + a.shape[1:], fill,
                                a.dtype)])
            pos = pad(np.asarray(r["pos"], np.int32), 0)
            res = _row_stats(
                jnp.take(h, jnp.asarray(pos), axis=0), norm, unembed,
                jnp.asarray(pad(r["x_tok"], -1)), jnp.asarray(pad(r["mode"], 0)),
                jnp.asarray(pad(r["key"], 0)), jnp.asarray(pad(r["probe"], -1)),
                qhat, eps=d["eps"], q8=quant == "int8")
            for k, v in zip(outs, res):
                outs[k].append(np.asarray(v)[:m])
    return {k: np.concatenate(v) if v else np.zeros(0) for k, v in outs.items()}
