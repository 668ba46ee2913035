"""The comparison that decides ``correct`` fails what it must, at the
smoke sizes on the CPU: the float8 control in the program's place, and
the program broken underneath the harness (a served token altered where
it is produced; a decode step that leaves the K/V state unchanged)."""

import jax.numpy as jnp
import pytest

import chipbench_testkit as kit
from repro.serve import engine

CELL = ("qwen2-smoke.closed", "qwen2-smoke", "closed")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return kit.bench_dir(tmp_path_factory.mktemp("control"))


@pytest.mark.parametrize("seed", [3, 2**32 + 9])
def test_program_passes_and_control_fails(root, seed):
    out = kit.run(kit.bench([CELL]), CELL[0], root, seed=seed, control=True)
    assert out["correct"] is False
    assert out["checks"]["max_logit_gap"]["value"] > kit.SMOKE_LIMIT
    assert out["program_gap"] <= kit.SMOKE_LIMIT


def _altered_token(self, *args):
    logits = engine.PagedDecoder.__dict__["_served"](self, *args)
    best = jnp.argmax(logits, axis=-1)
    return logits.at[jnp.arange(logits.shape[0]), (best + 1) % logits.shape[-1]
                     ].set(logits.max() + 1.0)


def _unchanged_state(self, *args):
    before = self.view.k_pool, self.view.v_pool
    logits = engine.PagedDecoder.__dict__["_served"](self, *args)
    self.view.k_pool, self.view.v_pool = before
    return logits


@pytest.mark.parametrize("fault", [_altered_token, _unchanged_state])
def test_broken_program_is_not_correct(root, monkeypatch, fault):
    monkeypatch.setattr(engine.PagedDecoder, "_served",
                        engine.PagedDecoder.decode_step, raising=False)
    monkeypatch.setattr(engine.PagedDecoder, "decode_step", fault)
    out = kit.run(kit.bench([CELL]), CELL[0], root, seed=5)
    assert out["correct"] is False
    assert out["checks"]["max_logit_gap"]["value"] > kit.SMOKE_LIMIT
