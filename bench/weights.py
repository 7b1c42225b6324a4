"""Seeded random weights for a decoder, made by the benchmark.

The weights are the benchmark's, not the program's: one jitted call on the
device builds the whole tree from ``--seed`` in the dtype the model is served
in, laid out the way the serving program takes its parameters:
``{"embed", "head", "final_norm", "blocks": [...]}``, one block per layer of
the period (``counts.period``), each leaf stacked over the period's
repeats. A block is ``{norm1, attn, norm2, mlp}``, or ``moe`` in ``mlp``'s
place on a routed layer (``attn_local`` layers have ``attn``'s leaves). The
program and the plain reference both read these same arrays.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

import counts

EMBED_STD = 0.02
NORM_SCALE_STD = 0.1  # RMSNorm gains are (1 + scale): nonzero so they count


def seed_key(seed: int) -> jax.Array:
    """A threefry key from any whole number, the large ones included."""
    words = np.random.SeedSequence(seed % 2**64).generate_state(2, np.uint32)
    return jnp.asarray(words, jnp.uint32)


# Each matrix leaf's contracted axes, counted in the stacked leaf (axis 0
# is the layer): its std is 1/sqrt(the product of their sizes).
FAN_IN = {
    ("attn", "wq"): (1,), ("attn", "wk"): (1,), ("attn", "wv"): (1,),
    ("attn", "wo"): (1, 2),
    ("mlp", "w_gate"): (1,), ("mlp", "w_up"): (1,), ("mlp", "w_down"): (1,),
    ("moe", "router"): (1,),
    ("moe", "w_gate"): (2,), ("moe", "w_up"): (2,), ("moe", "w_down"): (2,),
}
FLOAT32 = {("moe", "router")}  # kept in float32 whatever the served dtype


def _shapes(m: dict) -> dict:
    """Every leaf's shape, keyed like the serving program's tree."""
    kinds = counts.period(m)
    L = m["num_layers"] // len(kinds)  # each block stacks its repeats
    d, f, V = m["d_model"], m["d_ff"], m["vocab_size"]
    H, KH, dh = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    E, fe = m.get("num_experts", 0), m.get("expert_d_ff", 0) or f
    rms = m["norm"] == "rmsnorm"

    def block(mixer: str, mlp: str) -> dict:
        if mixer not in ("attn", "attn_local") or mlp not in ("mlp", "moe"):
            raise ValueError(f"no weights for a {mixer}/{mlp} layer")
        out = {
            "norm1": {"scale": (L, d)} if rms else {},
            "attn": {
                "wq": (L, d, H, dh),
                "wk": (L, d, KH, dh),
                "wv": (L, d, KH, dh),
                "wo": (L, H, dh, d),
            },
            "norm2": {"scale": (L, d)} if rms else {},
        }
        if mlp == "moe":
            out["moe"] = {
                "router": (L, d, E),
                "w_gate": (L, E, d, fe),
                "w_up": (L, E, d, fe),
                "w_down": (L, E, fe, d),
            }
        else:
            out["mlp"] = {"w_gate": (L, d, f), "w_up": (L, d, f), "w_down": (L, f, d)}
        return out

    return {
        "embed": {"embedding": (V, d)},
        "head": {} if m["tie_embeddings"] else {"lm_head": (d, V)},
        "final_norm": {"scale": (d,)} if rms else {},
        "blocks": [block(*k) for k in kinds],
    }


def _std(path: tuple, shape: tuple) -> float:
    name = path[-1]
    if name == "scale":
        return NORM_SCALE_STD
    if name in ("embedding", "lm_head"):
        return EMBED_STD
    fan_in = int(np.prod([shape[a] for a in FAN_IN[path[-2:]]]))
    return 1.0 / float(np.sqrt(fan_in))


def _tree_fn(model: dict):
    """The function from a key to the whole tree."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        _shapes(model), is_leaf=lambda x: isinstance(x, tuple)
    )
    paths = [  # each leaf's own keys: ("attn", "wq"), ("embed", "embedding"), ...
        tuple(k.key for k in p if hasattr(k, "key")) for p, _ in flat
    ]
    served = jnp.dtype(model["dtype"])

    def build(key):
        keys = jax.random.split(key, len(flat))
        out = [
            (jax.random.normal(k, shp, jnp.float32) * _std(p, shp)).astype(
                jnp.float32 if p[-2:] in FLOAT32 else served
            )
            for k, p, (_, shp) in zip(keys, paths, flat)
        ]
        return jax.tree.unflatten(treedef, out)

    return build


def make_params(model: dict, seed: int):
    """The whole parameter tree, made on the default device in one jitted
    call."""
    return jax.block_until_ready(jax.jit(_tree_fn(model))(seed_key(seed)))


def abstract_params(model: dict):
    """The tree's shapes and dtypes, with nothing allocated."""
    return jax.eval_shape(
        _tree_fn(model), jax.ShapeDtypeStruct((2,), jnp.uint32)
    )


def check_layout(params, expected) -> None:
    """Raise unless ``params`` has the tree and leaf shapes of ``expected``
    (the serving program's own abstract parameters)."""
    got = jax.tree.map(lambda x: (tuple(x.shape), str(x.dtype)), params)
    want = jax.tree.map(lambda x: (tuple(x.shape), str(x.dtype)), expected)
    if got != want:
        raise ValueError(
            f"the benchmark's weight layout no longer matches the program's: "
            f"{got} != {want}"
        )
