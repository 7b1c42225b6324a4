"""Plain float32 reference of a pre-norm decoder (OLMo, DeepSeek LLM, and
the same block with windowed layers and routed experts).

Written from the papers' equations, importing nothing of the serving
program: token embedding; per layer a norm, grouped-query causal attention
with rotary positions (the half-split rotation), a residual add, a norm, a
SwiGLU MLP and a residual add; a final norm and the output head (the
embedding itself where the model ties them). Norms are OLMo's
non-parametric LayerNorm or RMSNorm with a ``(1 + scale)`` gain.

The configuration's ``layer_pattern`` and ``mlp_pattern`` cycle over the
layers. An ``attn_local`` layer masks the keys ``sliding_window`` or more
positions behind the query. A ``moe`` layer routes each token without
dropping any: a float32 softmax router, its ``top_k`` experts with their
gates renormalised to sum to 1, each a SwiGLU MLP of width
``expert_d_ff``, summed by gate. Without those keys every layer is ``attn``
and ``mlp``.

Every matrix product runs in float32 at ``HIGHEST`` precision on weights
upcast from the served dtype. One sequence at a time, layer by layer
through ``lax.scan``, attention in blocks of query rows, the head only on the
rows asked for, so that it fits beside the parameters on one chip.

``quant`` computes the control, one precision below the served one, in
every product with a weight: ``"int8"`` (for a bfloat16 model) rounds the
weight with one absmax scale per output channel and its input with one per
row; ``"bf16"`` (for a float32 model) rounds both to bfloat16. The router
and the experts are weight products too. Attention's own products, norms and
softmaxes stay float32.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512
MIXERS = ("attn", "attn_local")
MLPS = ("mlp", "moe")
# settings of the model file this reference does not compute, at the value
# it assumes; any other value is refused
PLAIN = {
    "act": "silu", "qk_norm": False, "attn_logit_softcap": None,
    "final_logit_softcap": None, "embed_scale": False, "input_kind": "tokens",
}


def period(model: dict) -> tuple[tuple[str, str], ...]:
    """The (mixer, mlp) kind of each layer of one period."""
    mixers = tuple(model.get("layer_pattern", ("attn",)))
    mlps = tuple(model.get("mlp_pattern", ("mlp",)))
    n = int(np.lcm(len(mixers), len(mlps)))
    kinds = tuple((mixers[i % len(mixers)], mlps[i % len(mlps)]) for i in range(n))
    for mixer, mlp in kinds:
        if mixer not in MIXERS or mlp not in MLPS:
            raise ValueError(f"the reference has no {mixer}/{mlp} layer")
    for k, v in PLAIN.items():
        if model.get(k, v) != v:
            raise ValueError(f"the reference does not compute {k}={model[k]!r}")
    return kinds


def _bucket(n: int, least: int) -> int:
    b = least
    while b < n:
        b *= 2
    return b


def _int8(w: jax.Array, contract: tuple[int, ...]) -> jax.Array:
    """Round to int8 with one absmax scale per output channel."""
    w = w.astype(jnp.float32)
    scale = jnp.max(jnp.abs(w), axis=contract, keepdims=True) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return jnp.clip(jnp.round(w / scale), -127, 127) * scale


def _round(x: jax.Array, contract: tuple[int, ...], quant: str | None):
    """A product's operand in float32, rounded first for the control: int8
    with one scale per slice along the other axes than ``contract``."""
    if quant == "int8":
        return _int8(x, contract)
    if quant == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if quant is not None:
        raise ValueError(f"unknown control precision {quant!r}")
    return x.astype(jnp.float32)



def _norm(x, scale, eps):
    if scale is None:  # OLMo: LayerNorm without scale or bias
        mu = x.mean(-1, keepdims=True)
        var = jnp.square(x - mu).mean(-1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + eps)
    ms = jnp.square(x).mean(-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + eps) * (1.0 + scale)


def _rope(x, positions, theta):
    """x [S, heads, dh]; rotate the two halves of each head."""
    dh = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = positions[:, None].astype(jnp.float32) * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, kv_heads, window=None):
    """Causal GQA: q [S, H, dh], k/v [S, KH, dh] -> [S, H, dh], computed in
    blocks of ``Q_BLOCK`` query rows; with ``window``, only the keys fewer
    than ``window`` positions behind each query."""
    s, h, dh = q.shape
    g = h // kv_heads
    qb = q.reshape(s // Q_BLOCK, Q_BLOCK, kv_heads, g, dh)
    key_pos = jnp.arange(s)

    def one(args):
        i, qi = args
        sc = jnp.einsum("qhgd,khd->hgqk", qi, k, precision=HI) / np.sqrt(dh)
        q_pos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        ok = key_pos[None, :] <= q_pos[:, None]
        if window is not None:
            ok &= key_pos[None, :] > q_pos[:, None] - window
        sc = jnp.where(ok, sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("hgqk,khd->qhgd", p, v, precision=HI)

    out = jax.lax.map(one, (jnp.arange(s // Q_BLOCK), qb))
    return out.reshape(s, h, dh)


def _mlp(h, mp, quant):
    """SwiGLU of the normed rows ``h`` [S, d] -> [S, d]."""
    h = _round(h, (1,), quant)
    gate = jnp.einsum("sd,df->sf", h, _round(mp["w_gate"], (0,), quant),
                      precision=HI)
    up = jnp.einsum("sd,df->sf", h, _round(mp["w_up"], (0,), quant),
                    precision=HI)
    down = _round(mp["w_down"], (0,), quant)
    act = _round(jax.nn.silu(gate) * up, (1,), quant)
    return jnp.einsum("sf,fd->sd", act, down, precision=HI)


def _moe(h, mp, top_k, quant):
    """Every token through its ``top_k`` experts, none dropped: [S, d] ->
    [S, d]. The experts run one at a time over every row, each row weighted
    by its gate for that expert (0 where the expert is not its choice)."""
    logits = jnp.einsum("sd,de->se", _round(h, (1,), quant),
                        _round(mp["router"], (0,), quant), precision=HI)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, top_k)
    gates = gates / gates.sum(-1, keepdims=True)
    rows = jnp.arange(h.shape[0])[:, None]
    weight = jnp.zeros(probs.shape, jnp.float32).at[rows, idx].set(gates)

    def expert(acc, ew):
        w_gate, w_up, w_down, g = ew
        y = _mlp(h, {"w_gate": w_gate, "w_up": w_up, "w_down": w_down}, quant)
        return acc + g[:, None] * y, None

    out, _ = jax.lax.scan(
        expert, jnp.zeros(h.shape, jnp.float32),
        (mp["w_gate"], mp["w_up"], mp["w_down"], weight.T),
    )
    return out


@partial(jax.jit, static_argnames=("model", "quant"))
def _hidden(model: tuple, params: dict, tokens: jax.Array, quant: str | None):
    """Final-norm hidden states of one padded sequence: [S, d] float32.
    Weights are cast inside the layer loop, one layer at a time."""
    m = dict(model)
    eps, theta = m["norm_eps"], m["rope_theta"]
    pos = jnp.arange(tokens.shape[0])
    x = _round(params["embed"]["embedding"][tokens], (1,), quant)

    def layer(x, lw, mixer, mlp):
        a = lw["attn"]
        n1 = lw["norm1"].get("scale")
        n2 = lw["norm2"].get("scale")
        h = _norm(x, None if n1 is None else n1.astype(jnp.float32), eps)
        h = _round(h, (1,), quant)
        wq, wk, wv = (_round(a[k], (0,), quant) for k in ("wq", "wk", "wv"))
        q = _rope(jnp.einsum("sd,dhk->shk", h, wq, precision=HI), pos, theta)
        k = _rope(jnp.einsum("sd,dhk->shk", h, wk, precision=HI), pos, theta)
        v = jnp.einsum("sd,dhk->shk", h, wv, precision=HI)
        window = m["sliding_window"] if mixer == "attn_local" else None
        o = _attention(q, k, v, m["num_kv_heads"], window)
        wo = _round(a["wo"], (0, 1), quant)
        x = x + jnp.einsum("shk,hkd->sd", _round(o, (1, 2), quant), wo,
                           precision=HI)
        h = _norm(x, None if n2 is None else n2.astype(jnp.float32), eps)
        if mlp == "moe":
            return x + _moe(h, lw["moe"], m["top_k"], quant)
        return x + _mlp(h, lw["mlp"], quant)

    def one_period(x, blocks):
        for (mixer, mlp), lw in zip(m["period"], blocks):
            x = layer(x, lw, mixer, mlp)
        return x, None

    x, _ = jax.lax.scan(one_period, x, tuple(params["blocks"]))
    final = params["final_norm"].get("scale")
    return _norm(x, None if final is None else final.astype(jnp.float32), eps)


HEAD_BLOCK = 8192


@partial(jax.jit, static_argnames=("quant",))
def _head(params: dict, hidden_rows: jax.Array, quant: str | None):
    """Logits of the given hidden rows, the vocabulary in blocks."""
    if params["head"]:
        table = params["head"]["lm_head"].T  # [V, d]
    else:
        table = params["embed"]["embedding"]
    v, d = table.shape
    nb = -(-v // HEAD_BLOCK)
    table = jnp.pad(table, ((0, nb * HEAD_BLOCK - v), (0, 0)))
    blocks = table.reshape(nb, HEAD_BLOCK, d)

    rows = _round(hidden_rows, (1,), quant)

    def one(tb):
        # one scale per vocabulary entry: the head's output channel
        return jnp.einsum("rd,vd->rv", rows, _round(tb, (1,), quant),
                          precision=HI)

    out = jax.lax.map(one, blocks)  # [nb, R, HEAD_BLOCK]
    return jnp.moveaxis(out, 0, 1).reshape(hidden_rows.shape[0], -1)[:, :v]


def logits_rows(
    model: dict, params: dict, tokens: np.ndarray, rows: np.ndarray,
    quant: str | None = None,
) -> np.ndarray:
    """Logits [len(rows), vocab] at positions ``rows`` of ``tokens``."""
    n = len(tokens)
    padded = np.zeros(_bucket(n, Q_BLOCK), np.int32)
    padded[:n] = tokens
    kinds = period(model)
    if len(kinds) != len(params["blocks"]):
        raise ValueError(
            f"a period of {len(kinds)} layers, weights for {len(params['blocks'])}"
        )
    key = tuple(sorted(
        (k, model.get(k)) for k in
        ("num_kv_heads", "norm", "norm_eps", "rope_theta", "sliding_window",
         "top_k")
    )) + (("period", kinds),)
    hidden = _hidden(key, params, jnp.asarray(padded), quant)
    r = np.zeros(_bucket(len(rows), 16), np.int32)
    r[: len(rows)] = rows
    out = _head(params, hidden[jnp.asarray(r)], quant)
    return np.asarray(out)[: len(rows)]
