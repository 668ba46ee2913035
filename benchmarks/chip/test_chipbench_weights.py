"""The seeded weights: the dense-GQA tree for a seed stays bit-identical
as its code moves, and a layout's ``INIT`` overrides the rule a leaf's
name selects."""

import hashlib
import types

import jax
import numpy as np

import chipbench_testkit as kit
from chipbench import harness, weights

GQA = harness.load_module(kit.BENCH_DIR, "layouts", "dense_gqa")
SEED = 2**33 + 5
# ``weights.make`` of the qwen2-smoke configuration for SEED, taken before
# the weight tree moved from ``chipbench/weights.py`` into
# ``layouts/dense_gqa.py``: each leaf's path, shape and dtype, and the
# sha256 of their bytes in that order.
QWEN2_SMOKE_LEAVES = [
    ("['embed']", (256, 64)), ("['final_norm']", (64,)),
    ("['layers']['attn_norm']", (4, 64)), ("['layers']['bk']", (4, 32)),
    ("['layers']['bq']", (4, 64)), ("['layers']['bv']", (4, 32)),
    ("['layers']['mlp_norm']", (4, 64)),
    ("['layers']['w_down']", (4, 128, 64)),
    ("['layers']['w_gate']", (4, 64, 128)),
    ("['layers']['w_up']", (4, 64, 128)), ("['layers']['wk']", (4, 64, 32)),
    ("['layers']['wo']", (4, 64, 64)), ("['layers']['wq']", (4, 64, 64)),
    ("['layers']['wv']", (4, 64, 32)),
]
QWEN2_SMOKE_SHA256 = (
    "e75e2f62d6062dcea843666d3ccae5da4f3254c5a7991e1322327e1045a5c0f8")


def test_qwen2_smoke_weights_match_the_pinned_checksum():
    cfg = {"name": "qwen2-smoke", **kit.COMMON, **kit.SMOKE["qwen2-smoke"]}
    leaves = jax.tree.flatten_with_path(weights.make(GQA, cfg, SEED))[0]
    assert [(jax.tree_util.keystr(p), x.shape) for p, x in leaves] == \
        QWEN2_SMOKE_LEAVES
    assert {str(x.dtype) for _, x in leaves} == {"bfloat16"}
    h = hashlib.sha256()
    for _, x in leaves:
        h.update(np.asarray(x).tobytes())
    assert h.hexdigest() == QWEN2_SMOKE_SHA256


def test_layout_init_overrides_the_name_rule():
    # a router's score-correction bias [layers, experts]: by its name a
    # matrix, which ``fan_in`` would scale by layers ** -0.5
    shapes = {"layers": {"router": (2, 16, 8), "router_bias": (2, 8)}}
    plain = types.SimpleNamespace(shapes=lambda cfg: shapes, INIT={})
    over = types.SimpleNamespace(
        shapes=lambda cfg: shapes,
        INIT={"['layers']['router_bias']": "zeros"})
    cfg = {"torch_dtype": "float32"}
    a, b = weights.make(plain, cfg, SEED), weights.make(over, cfg, SEED)
    assert float(np.abs(np.asarray(a["layers"]["router_bias"])).max()) > 0
    assert not np.asarray(b["layers"]["router_bias"]).any()
    np.testing.assert_array_equal(a["layers"]["router"],
                                  b["layers"]["router"])
