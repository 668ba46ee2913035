"""A CPU rehearsal of a run: the harness's inner loop in-process on the
smoke configurations, a few steps each, and the command's refusal of a
CPU. Files are found by name: a configuration, a mix, a metric and an
architecture (its reference and its layout, with its own weights,
hand-over to the program and work counts) added as new files are run with
no edit to any file already there."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

import chipbench_testkit as kit
from chipbench import harness

KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return kit.bench_dir(tmp_path_factory.mktemp("rehearsal"))


def test_open_loop_run_prints_the_result_line(root):
    b = kit.bench([("qwen2-smoke.agent", "qwen2-smoke", "agent")])
    out = kit.run(b, "qwen2-smoke.agent", root)
    assert list(out) == KEYS
    line = json.loads(json.dumps(out))
    assert line["correct"] is True
    # a reader with nothing to read (no two tokens of one request in a
    # short CPU window: no gap) leaves its metric out
    assert {"output_tok_per_s", "setup_s"} <= set(line["metrics"]) <= \
        {"itl_p95_s", "output_tok_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["device"]["platform"] == "cpu"
    assert line["checks"]["max_logit_gap"]["limit"] == kit.SMOKE_LIMIT
    assert line["checks"]["served_tokens_checked"]["value"] >= 1


def test_new_config_mix_and_metric_files_are_found_by_name(root):
    cfg = json.loads((root / "configs" / "internlm2-smoke.json").read_text())
    kit.write(root, "configs", "internlm2-smoke-b", {**cfg, "name":
                                                     "internlm2-smoke-b"})
    kit.write(root, "traffic", "closed-b", {**kit.MIXES["closed"],
                                            "clients": 3})
    kit.write(root, "limits", "internlm2-smoke-b.closed-b",
              {"max_logit_gap": {"limit": kit.SMOKE_LIMIT}})
    (root / "metrics" / "steps_in_window.py").write_text(
        "def read(rec):\n    return rec.steps\n")
    b = kit.bench([("internlm2-smoke-b.closed-b", "internlm2-smoke-b",
                    "closed-b")])
    b["end_to_end"].append({"name": "steps_in_window", "unit": "count",
                            "better": "higher", "bound": 0.25,
                            "source": "host_clock"})
    out = kit.run(b, "internlm2-smoke-b.closed-b", root)
    assert list(out) == KEYS and out["correct"] is True
    assert out["metrics"]["steps_in_window"]["value"] >= 1
    assert out["attempted"] >= 0 and out["failed"] == 0


def test_missing_file_is_an_error(root):
    b = kit.bench([("nope.agent", "nope", "agent")])
    with pytest.raises(FileNotFoundError, match="nope"):
        kit.run(b, "nope.agent", root)


def test_command_refuses_a_cpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run(
        [sys.executable, str(kit.BENCH_DIR / "run.py"), "--workload",
         "qwen2-0.5b.batch-decode", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        cwd=kit.CHECKOUT, timeout=120)
    assert p.returncode != 0
    assert "needs 1 TPU" in p.stderr
    assert "{" not in p.stdout


# Dense GQA stored with the query, key and value projections fused into one
# ``wqkv`` [layers, d, (heads + 2 * kv_heads) * hd] (and ``bqkv``): the
# layout splits it for the program, and the reference reads it fused.
FUSED_LAYOUT = textwrap.dedent('''
    import pathlib

    import jax.numpy as jnp

    from chipbench.harness import load_module

    _gqa = load_module(pathlib.Path(__file__).resolve().parents[1],
                       "layouts", "dense_gqa")
    INIT = {}
    program_config = _gqa.program_config
    warm_decode_call = _gqa.warm_decode_call
    prefill_attention = _gqa.prefill_attention
    token_flops, chunk_flops = _gqa.token_flops, _gqa.chunk_flops
    returned = []            # every Work decode_attention returned


    def shapes(cfg):
        out = _gqa.shapes(cfg)
        lw = out["layers"]
        nl, d, q = lw.pop("wq")
        kv = lw.pop("wk")[-1] + lw.pop("wv")[-1]
        lw["wqkv"] = (nl, d, q + kv)
        if "bq" in lw:
            lw["bqkv"] = (nl, lw.pop("bq")[-1] + lw.pop("bk")[-1]
                          + lw.pop("bv")[-1])
        return out


    def split(lw, fused, names):
        q = lw["wo"].shape[-2]
        kv = (lw[fused].shape[-1] - q) // 2
        return dict(zip(names, jnp.split(lw[fused], [q, q + kv], axis=-1)))


    def program_params(w):
        lw = {k: v for k, v in w["layers"].items() if "qkv" not in k}
        lw.update(split(w["layers"], "wqkv", ("wq", "wk", "wv")))
        if "bqkv" in w["layers"]:
            lw.update(split(w["layers"], "bqkv", ("bq", "bk", "bv")))
        return _gqa.program_params({**w, "layers": lw})


    def decode_attention(cfg, page_size, contexts):
        work = _gqa.decode_attention(cfg, page_size, contexts)
        returned.append(work)
        return work
''')
FUSED_REFERENCE = textwrap.dedent('''
    import pathlib

    import jax.numpy as jnp

    from chipbench.harness import load_module

    _gqa = load_module(pathlib.Path(__file__).resolve().parents[1],
                       "references", "dense_gqa")


    def logit_gaps(cfg, w, tokens, first_served, with_control=False):
        lw = dict(w["layers"])
        q = cfg["num_attention_heads"] * cfg["head_dim"]
        kv = cfg["num_key_value_heads"] * cfg["head_dim"]
        lw["wq"], lw["wk"], lw["wv"] = jnp.split(lw.pop("wqkv"),
                                                 [q, q + kv], axis=-1)
        if "bqkv" in lw:
            lw["bq"], lw["bk"], lw["bv"] = jnp.split(lw.pop("bqkv"),
                                                     [q, q + kv], axis=-1)
        return _gqa.logit_gaps(cfg, {**w, "layers": lw}, tokens,
                               first_served, with_control)
''')


def test_new_architecture_is_found_by_name(root, monkeypatch):
    (root / "layouts" / "fused_gqa.py").write_text(FUSED_LAYOUT)
    (root / "references" / "fused_gqa.py").write_text(FUSED_REFERENCE)
    cfg = json.loads((root / "configs" / "qwen2-smoke.json").read_text())
    kit.write(root, "configs", "qwen2-fused", {**cfg, "name": "qwen2-fused",
                                               "reference": "fused_gqa"})
    kit.write(root, "limits", "qwen2-fused.closed",
              {"max_logit_gap": {"limit": kit.SMOKE_LIMIT}})
    loaded, recs = {}, []
    load = harness.load_module

    def spy(bench_dir, kind, name):
        loaded[kind, name] = mod = load(bench_dir, kind, name)
        return mod

    class Rec(harness.Record):
        def __init__(self, *args, **kw):
            super().__init__(*args, **kw)
            recs.append(self)

    monkeypatch.setattr(harness, "load_module", spy)
    monkeypatch.setattr(harness, "Record", Rec)
    b = kit.bench([("qwen2-fused.closed", "qwen2-fused", "closed")])
    out = kit.run(b, "qwen2-fused.closed", root)
    assert list(out) == KEYS and out["correct"] is True
    assert out["checks"]["served_tokens_checked"]["value"] >= 1
    layout = loaded["layouts", "fused_gqa"]
    (rec,) = recs
    assert rec.decode_launches
    assert len(rec.decode_launches) == len(layout.returned)
    assert all(a is b for a, b in zip(rec.decode_launches, layout.returned))


def test_missing_layout_names_the_file(root):
    (root / "references" / "orphan.py").write_text(
        (root / "references" / "dense_gqa.py").read_text())
    cfg = json.loads((root / "configs" / "qwen2-smoke.json").read_text())
    kit.write(root, "configs", "orphan", {**cfg, "name": "orphan",
                                          "reference": "orphan"})
    kit.write(root, "limits", "orphan.agent",
              {"max_logit_gap": {"limit": kit.SMOKE_LIMIT}})
    b = kit.bench([("orphan.agent", "orphan", "agent")])
    with pytest.raises(FileNotFoundError,
                       match=r"no layouts module .*layouts/orphan\.py"):
        kit.run(b, "orphan.agent", root)
