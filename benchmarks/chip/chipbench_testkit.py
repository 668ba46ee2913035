"""What the benchmark's CPU tests share: a bench directory in a temporary
place with the committed metric readers and references and configurations
cut to the registry's smoke sizes, and a driver for ``harness.run``.

Only the tests import this module; they run with ``JAX_PLATFORMS=cpu``,
where the engine serves through the jnp reference attention.
"""

from __future__ import annotations

import json
import pathlib
import shutil
import time

from chipbench import harness, roofline

BENCH_DIR = pathlib.Path(__file__).resolve().parent
CHECKOUT = BENCH_DIR.parents[1]

# The registry's smoke configurations (``qwen2_0_5b.smoke_config``,
# ``internlm2_20b.smoke_config``) in the configuration files' keys.
SMOKE = {
    "qwen2-smoke": {
        "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 4,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "vocab_size": 256, "qkv_bias": True, "tie_word_embeddings": True},
    "internlm2-smoke": {
        "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 4,
        "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 8,
        "vocab_size": 256, "qkv_bias": False, "tie_word_embeddings": False},
}
COMMON = {"source": "test", "reference": "dense_gqa", "rms_norm_eps": 1e-6,
          "rope_theta": 10000.0, "torch_dtype": "bfloat16", "page_size": 16,
          "max_batch": 8, "prefill_token_budget": 64, "kv_pool_pages": 256,
          "reduced": [], "assumed": {}}
MIXES = {
    "agent": {"loop": "open", "rate_per_s": 4.0, "interarrival": {"cv": 2.0},
              "shared_prefix": {"count": 2, "tokens": 64, "zipf_s": 1.0},
              "prompt": {"median": 16, "sigma": 1.0, "min": 4, "max": 64},
              "output": {"median": 6, "sigma": 0.8, "min": 2, "max": 16}},
    "closed": {"loop": "closed", "clients": 4,
               "prompt": {"median": 16, "sigma": 1.0, "min": 4, "max": 64},
               "output": {"median": 8, "sigma": 0.5, "min": 4, "max": 32}},
}
# Readings of the smoke cells on the CPU: the program's widest gap stays
# under 2e-3, the float8 control's reads 2e-2 and more.
SMOKE_LIMIT = 8e-3
FAKE_PEAKS = roofline.Peaks(1e12, 1e11, 1e10, "a stand-in for the CPU")


def bench_dir(tmp: pathlib.Path) -> pathlib.Path:
    """A bench directory under ``tmp``: the committed readers and
    references, the smoke configurations and mixes, and a limits file for
    each of their cells."""
    root = tmp / "bench"
    for kind in ("metrics", "references"):
        shutil.copytree(BENCH_DIR / kind, root / kind,
                        ignore=shutil.ignore_patterns("__pycache__"))
    for kind in ("configs", "traffic", "limits"):
        (root / kind).mkdir(parents=True)
    for name, sizes in SMOKE.items():
        write(root, "configs", name, {"name": name, **COMMON, **sizes})
        for mix in MIXES:
            write(root, "limits", f"{name}.{mix}",
                  {"max_logit_gap": {"limit": SMOKE_LIMIT}})
    for name, mix in MIXES.items():
        write(root, "traffic", name, mix)
    return root


def write(root: pathlib.Path, kind: str, name: str, obj) -> None:
    (root / kind / f"{name}.json").write_text(json.dumps(obj))


def bench(cells) -> dict:
    """``BENCHMARK.json`` with its metrics, over ``cells`` of
    (name, config, mix)."""
    b = json.loads((CHECKOUT / "BENCHMARK.json").read_text())
    b["workloads"] = [{"name": n, "config": c, "traffic": t, "chips": 1,
                       "why": "CPU rehearsal"} for n, c, t in cells]
    for m in b["end_to_end"] + b["per_layer"]:
        m["workloads"] = [n for n, _, _ in cells]
    return b


def run(b: dict, cell: str, root: pathlib.Path, *, seed: int = 2**33 + 5,
        seconds: float = 2.0, **kw) -> dict:
    return harness.run(b, cell, seed, seconds, False,
                       t_start=time.perf_counter(), peaks=FAKE_PEAKS,
                       bench_dir=root, log=lambda msg: None, **kw)
