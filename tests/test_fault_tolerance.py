"""Fault tolerance: checkpoint/restart, failure recovery, elastic resharding,
straggler mitigation, gradient compression."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.ckpt import CheckpointManager
from repro.configs import registry
from repro.data.pipeline import BwapDataRouter, PrefetchLoader, \
    ShardedTokenDataset
from repro.models.lm import LM
from repro.train import optimizer as opt_mod
from repro.train.loop import LoopConfig, SimulatedFailure, Trainer


def _tiny():
    cfg = registry.get_smoke_config("qwen2-0.5b")
    cfg = dataclasses.replace(cfg, num_layers=2, d_ff=64)
    return cfg, LM(cfg)


def _batch_fn(cfg, bs=4, s=16):
    def f(step):
        rng = np.random.default_rng(step)
        return {"tokens": jnp.asarray(
            rng.integers(0, cfg.vocab_size, (bs, s)), jnp.int32)}
    return f


def test_checkpoint_roundtrip_and_hash(tmp_path):
    cm = CheckpointManager(tmp_path)
    tree = {"a": jnp.arange(12.0).reshape(3, 4),
            "b": {"c": jnp.ones((5,), jnp.int32)}}
    cm.save(7, tree, metadata={"x": 1})
    step, out = cm.restore(like=tree)
    assert step == 7
    np.testing.assert_array_equal(out["a"], tree["a"])
    np.testing.assert_array_equal(out["b"]["c"], tree["b"]["c"])
    # corrupt a tensor file -> integrity error
    f = next((tmp_path / "step_0000000007").glob("arr_*.npy"))
    raw = bytearray(f.read_bytes())
    raw[-1] ^= 0xFF
    f.write_bytes(bytes(raw))
    with pytest.raises(IOError):
        cm.restore(like=tree)


def test_checkpoint_gc_keeps_last(tmp_path):
    cm = CheckpointManager(tmp_path, keep_last=2)
    t = {"a": jnp.zeros(3)}
    for s in (1, 2, 3, 4):
        cm.save(s, t)
    names = sorted(p.name for p in tmp_path.glob("step_*"))
    assert names == ["step_0000000003", "step_0000000004"]


def test_crash_restart_resumes_identically(tmp_path):
    """A crash mid-run restarts from the checkpoint and converges to the
    same state as an uninterrupted run (deterministic data + updates)."""
    cfg, model = _tiny()
    ocfg = opt_mod.OptConfig(lr=1e-3, warmup_steps=2, total_steps=12)

    def trainer(d):
        return Trainer(model, ocfg, LoopConfig(total_steps=12, ckpt_every=4,
                                               log_every=100),
                       str(d), _batch_fn(cfg))

    # uninterrupted
    t1 = trainer(tmp_path / "a")
    _, p_ref, _, m_ref = t1.run()

    # crash at step 6, then restart from LATEST (step 4)
    t2 = Trainer(model, ocfg,
                 LoopConfig(total_steps=12, ckpt_every=4, log_every=100,
                            fail_at_step=6), str(tmp_path / "b"),
                 _batch_fn(cfg))
    with pytest.raises(SimulatedFailure):
        t2.run()
    t3 = trainer(tmp_path / "b")   # no fail_at_step: resumes at 4
    step, p_resumed, _, m_res = t3.run()
    assert step == 12
    flat1 = jax.tree.leaves(p_ref)
    flat2 = jax.tree.leaves(p_resumed)
    for a, b in zip(flat1, flat2):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=2e-4, atol=2e-4)


def test_elastic_restore_across_meshes(tmp_path):
    """Checkpoints are mesh-independent: train on an 8-device mesh, lose
    half the hosts, restore onto a 4-device mesh and continue. Runs in a
    subprocess so the host-device-count flag stays scoped (conftest must
    see 1 device)."""
    import subprocess
    import sys
    import textwrap
    script = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import registry
        from repro.models.lm import LM
        from repro.checkpoint.ckpt import CheckpointManager
        from repro.sharding import specs as sh

        cfg = registry.get_smoke_config("qwen2-0.5b")
        cfg = dataclasses.replace(cfg, num_layers=2, d_ff=64)
        model = LM(cfg)
        params = model.init(jax.random.PRNGKey(0))
        cm = CheckpointManager(r"{tmp_path}")
        cm.save(3, params)

        def mesh_of(n):
            return jax.make_mesh(
                (n // 2, 2), ("data", "model"),
                axis_types=(jax.sharding.AxisType.Auto,) * 2)

        for ndev in (8, 4):     # full fleet, then degraded fleet
            mesh = mesh_of(ndev)
            shards = sh.param_shardings(cfg, mesh, jax.eval_shape(
                lambda: model.init(jax.random.PRNGKey(0))))
            step, restored = cm.restore(like=params, shardings=shards)
            batch = {{"tokens": jnp.zeros((4, 8), jnp.int32)}}
            with mesh:
                loss, _ = jax.jit(model.loss)(restored, batch)
            assert jnp.isfinite(loss), ndev
        print("ELASTIC_OK")
    """)
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, env={**__import__("os").environ,
                                       "PYTHONPATH": "src"},
                       cwd=str(pathlib_root()), timeout=300)
    assert "ELASTIC_OK" in r.stdout, r.stderr[-2000:]


def pathlib_root():
    import pathlib
    return pathlib.Path(__file__).resolve().parents[1]


def test_straggler_rebalancing_moves_shards():
    router = BwapDataRouter(num_shards=64, host_bws=[1.0, 1.0, 1.0, 1.0])
    before = router.shards_of(3).size
    # host 3 is 5x slower than the others
    for _ in range(12):
        for h in range(4):
            router.record_fetch(h, 0.05 if h != 3 else 0.25)
    after = router.shards_of(3).size
    assert after < before
    assert router.migrations > 0
    # all shards still owned exactly once
    assert sum(router.shards_of(h).size for h in range(4)) == 64


def test_prefetch_loader_yields_deterministic_batches():
    ds = ShardedTokenDataset(vocab_size=97, seq_len=8, num_shards=4, seed=1)
    router = BwapDataRouter(4, [1, 1, 1, 1])
    loader = PrefetchLoader(ds, router, host=0, batch_size=2)
    s1, b1 = next(loader)
    loader.close()
    b_again = ds.batch(int(router.shards_of(0)[s1 % router.shards_of(0).size]
                           ) if len(router.shards_of(0)) else 0, s1, 2)
    assert b1.shape == (2, 8)
    assert b1.dtype == np.int32


def test_grad_compression_error_feedback():
    """int8 psum with error feedback: single-step error is bounded; the
    residual carries what was rounded away."""
    from repro.train import compress
    mesh = jax.make_mesh((1,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    g = {"w": jnp.asarray(np.random.default_rng(0).normal(
        size=(64, 64)).astype(np.float32))}
    r = compress.init_residuals(g)
    red, new_r = compress.compressed_psum_grads(g, r, mesh)
    err = np.abs(np.asarray(red["w"]) - np.asarray(g["w"]))
    scale = np.abs(np.asarray(g["w"])).max() / 127.0
    assert err.max() <= scale * 1.01
    # error feedback: residual == what was lost
    np.testing.assert_allclose(np.asarray(new_r["w"]),
                               np.asarray(g["w"]) - np.asarray(red["w"]),
                               rtol=1e-5, atol=1e-6)


def test_quantized_adam_moments_roundtrip():
    from repro.train.optimizer import dequantize_q8, quantize_q8
    x = jnp.asarray(np.random.default_rng(1).normal(size=(1000,))
                    .astype(np.float32))
    q = quantize_q8(x, 256)
    back = dequantize_q8(q, x.shape)
    err = np.abs(np.asarray(back) - np.asarray(x))
    # blockwise absmax: error bounded by scale/2 per block
    assert err.max() < np.abs(np.asarray(x)).max() / 127.0
