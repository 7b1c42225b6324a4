"""The benchmark's weights and plain reference: a dense configuration draws
and reads exactly as it did before layers had kinds, and a patterned one
(windowed layers, routed experts) has the program's layout and the
reference's published semantics."""

import hashlib
import importlib.util
import json
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
DATA = BENCH / "tests" / "data"
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402
import weights  # noqa: E402

SEED = 2**31 + 101


def _model(name):
    return json.loads((DATA / f"{name}.json").read_text())["model"]


def _reference():
    path = BENCH / "reference" / "dense_decoder.py"
    spec = importlib.util.spec_from_file_location("bench_ref_test", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _digest(params) -> str:
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()


# tiny.json's tree as the single-block draw made it, every leaf's bytes
@pytest.mark.parametrize("seed,digest", [
    (SEED, "4dce55f1a8f75997c8c548d28b65768e0cd13422e912ffff2077dda3e0223b2a"),
    (7, "70b989902ae043846a36f5365a36c6931c3480470c95b3c4cec9ddb804434cfb"),
])
def test_dense_weights_are_bitwise_as_before(seed, digest):
    params = weights.make_params(_model("tiny"), seed)
    assert len(params["blocks"]) == 1
    assert _digest(params) == digest


# the dense reference's logits at 20 positions of one sequence, before the
# reference walked layer kinds: the sum of their magnitudes, their largest
# value and each row's best token
@pytest.mark.parametrize("quant,abs_sum,top,best4", [
    (None, 10698.695560226894, 0.713752806186676, 1611),
    ("bf16", 10700.340480677434, 0.713402271270752, 1611),
    ("int8", 10701.093316371047, 0.7183965444564819, 2814),
])
def test_dense_reference_reads_as_before(quant, abs_sum, top, best4):
    m = _model("tiny")
    params = weights.make_params(m, SEED)
    tokens = np.random.default_rng(5).integers(0, m["vocab_size"], 40)
    out = _reference().logits_rows(
        m, params, tokens.astype(np.int32), np.arange(20, 40), quant=quant
    ).astype(np.float64)
    assert float(np.abs(out).sum()) == pytest.approx(abs_sum, rel=1e-6)
    assert float(out.max()) == pytest.approx(top, rel=1e-6)
    assert int(out[4].argmax()) == best4


def test_patterned_blocks_follow_the_period():
    m = _model("tiny-patterned")
    params = weights.make_params(m, SEED)
    blocks = params["blocks"]
    assert len(blocks) == 4  # lcm of a 4-layer and a 1-layer pattern
    for b in blocks:
        assert set(b) == {"norm1", "attn", "norm2", "moe"}
        assert b["moe"]["router"].shape == (1, 64, 4)
        assert b["moe"]["router"].dtype == np.float32
        assert b["moe"]["w_gate"].shape == (1, 4, 64, 32)
        assert b["moe"]["w_down"].shape == (1, 4, 32, 64)
    # fan-in is the contracted axes: d for the router and an expert's
    # gate, the expert width for its down projection
    std = float(np.std(np.asarray(blocks[0]["moe"]["w_down"])))
    assert std == pytest.approx(1 / np.sqrt(32), rel=0.05)
    std = float(np.std(np.asarray(blocks[0]["moe"]["w_gate"])))
    assert std == pytest.approx(1 / np.sqrt(64), rel=0.05)


def test_mellum2_shaped_layout_by_eval_shape():
    """Mellum2-12B-A2.5B at published widths, two whole periods of three
    windowed layers and a full one, all 64 experts: the benchmark's tree is
    the program's, from shapes alone."""
    from repro import models

    m = {
        "num_layers": 8, "d_model": 2304, "num_heads": 32, "num_kv_heads": 4,
        "head_dim": 128, "d_ff": 7168, "vocab_size": 98304, "context": 4096,
        "norm": "rmsnorm", "norm_eps": 1e-06, "rope_theta": 500000.0,
        "tie_embeddings": False, "dtype": "bfloat16",
        "layer_pattern": ["attn_local"] * 3 + ["attn"], "sliding_window": 1024,
        "mlp_pattern": ["moe"], "num_experts": 64, "top_k": 8,
        "expert_d_ff": 896,
    }
    arch = harness.arch_config({"name": "mellum2", "model": m})
    want = jax.eval_shape(lambda: models.init_params(arch, jax.random.PRNGKey(0)))
    got = weights.abstract_params(m)
    weights.check_layout(got, want)
    nbytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(got))
    assert 7.5e9 < nbytes < 7.7e9  # about 7.6 GB of weights


def test_a_window_wider_than_the_sequence_is_full_attention():
    m = _model("tiny-patterned")
    params = weights.make_params(m, SEED)
    tokens = np.random.default_rng(3).integers(0, 4096, 60).astype(np.int32)
    rows = np.arange(30, 60)
    ref = _reference()
    wide = ref.logits_rows({**m, "sliding_window": 4096}, params, tokens, rows)
    full = ref.logits_rows({**m, "layer_pattern": ["attn"] * 4}, params, tokens,
                           rows)
    np.testing.assert_array_equal(wide, full)
    # the window of 24 changes what the later rows see
    assert np.abs(ref.logits_rows(m, params, tokens, rows) - full).max() > 1e-3


def test_routed_gates_sum_to_one():
    """Four copies of one expert, each token routed to two: the layer is
    that expert's MLP, whatever the router says."""
    m = _model("tiny-patterned")
    params = weights.make_params(m, SEED)
    same = jax.tree.map(lambda x: x, params)
    for b in same["blocks"]:
        for k in ("w_gate", "w_up", "w_down"):
            b["moe"][k] = np.broadcast_to(b["moe"][k][:, :1], b["moe"][k].shape)
    dense = {**m, "mlp_pattern": ["mlp"], "d_ff": 32}
    as_mlp = jax.tree.map(lambda x: x, params)
    for b, s in zip(as_mlp["blocks"], same["blocks"]):
        b["mlp"] = {k: s["moe"][k][:, 0] for k in ("w_gate", "w_up", "w_down")}
        del b["moe"]
    tokens = np.random.default_rng(4).integers(0, 4096, 40).astype(np.int32)
    rows = np.arange(20, 40)
    ref = _reference()
    np.testing.assert_allclose(
        ref.logits_rows(m, same, tokens, rows),
        ref.logits_rows(dense, as_mlp, tokens, rows), rtol=1e-5, atol=1e-6,
    )


def test_the_reference_refuses_what_it_does_not_compute():
    ref = _reference()
    for bad in ({"act": "gelu"}, {"attn_logit_softcap": 50.0},
                {"layer_pattern": ["mamba"]}, {"qk_norm": True}):
        with pytest.raises(ValueError):
            ref.period({**_model("tiny"), **bad})
    m = _model("tiny-patterned")  # a period the weights were not made for
    params = weights.make_params(m, SEED)
    with pytest.raises(ValueError):
        ref.logits_rows({**m, "layer_pattern": ["attn"]}, params,
                        np.arange(8, dtype=np.int32), np.arange(8))
