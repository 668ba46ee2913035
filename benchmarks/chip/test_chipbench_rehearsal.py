"""A CPU rehearsal of a run: the harness's inner loop in-process on the
smoke configurations, a few steps each, and the command's refusal of a
CPU. Files are found by name: a configuration, a mix and a metric added as
new files are run with no edit to any file already there."""

import json
import os
import subprocess
import sys

import pytest

import chipbench_testkit as kit

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
