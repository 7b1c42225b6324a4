"""Seeded random weights for a dense decoder, made by the benchmark.

The weights are the benchmark's, not the program's: one jitted call on the
device builds the whole tree from ``--seed`` in the dtype the model is served
in, laid out the way the serving program takes its parameters
(``{"embed", "head", "final_norm", "blocks": [ {norm1, attn, norm2, mlp} ]}``,
each block leaf stacked over the layers). The program and the plain
reference both read these same arrays.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

EMBED_STD = 0.02
NORM_SCALE_STD = 0.1  # RMSNorm gains are (1 + scale): nonzero so they count


def seed_key(seed: int) -> jax.Array:
    """A threefry key from any whole number, the large ones included."""
    words = np.random.SeedSequence(seed % 2**64).generate_state(2, np.uint32)
    return jnp.asarray(words, jnp.uint32)


def _shapes(m: dict) -> dict:
    """Every leaf's shape, keyed like the serving program's tree."""
    L, d, f, V = m["num_layers"], m["d_model"], m["d_ff"], m["vocab_size"]
    H, KH, dh = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    rms = m["norm"] == "rmsnorm"
    block = {
        "norm1": {"scale": (L, d)} if rms else {},
        "attn": {
            "wq": (L, d, H, dh),
            "wk": (L, d, KH, dh),
            "wv": (L, d, KH, dh),
            "wo": (L, H, dh, d),
        },
        "norm2": {"scale": (L, d)} if rms else {},
        "mlp": {"w_gate": (L, d, f), "w_up": (L, d, f), "w_down": (L, f, d)},
    }
    return {
        "embed": {"embedding": (V, d)},
        "head": {} if m["tie_embeddings"] else {"lm_head": (d, V)},
        "final_norm": {"scale": (d,)} if rms else {},
        "blocks": [block],
    }


def _std(name: str, shape: tuple) -> float:
    if name == "scale":
        return NORM_SCALE_STD
    if name in ("embedding", "lm_head"):
        return EMBED_STD
    # stacked [L, fan_in, ...]; the output projection contracts heads x dh
    fan_in = shape[1] * (shape[2] if name == "wo" else 1)
    return 1.0 / float(np.sqrt(fan_in))


def make_params(model: dict, seed: int):
    """The whole parameter tree, made on the default device in one jitted
    call."""
    shapes = _shapes(model)
    leaves, treedef = jax.tree.flatten(
        shapes, is_leaf=lambda x: isinstance(x, tuple)
    )
    paths = [  # each leaf's own key: "wq", "embedding", "scale", ...
        p[-1].key
        for p, _ in jax.tree_util.tree_flatten_with_path(
            shapes, is_leaf=lambda x: isinstance(x, tuple)
        )[0]
    ]
    dtype = jnp.dtype(model["dtype"])

    def build(key):
        keys = jax.random.split(key, len(leaves))
        out = [
            (jax.random.normal(k, shp, jnp.float32) * _std(p, shp)).astype(dtype)
            for k, p, shp in zip(keys, paths, leaves)
        ]
        return jax.tree.unflatten(treedef, out)

    return jax.block_until_ready(jax.jit(build)(seed_key(seed)))


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
