"""Fault-tolerance integration tests: checkpoint/restart, determinism,
crash injection, compression, serving."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.ckpt import Checkpointer, ckpt_path, latest_step, restore_pytree, save_pytree
from repro.launch.serve import serve_batch
from repro.launch.train import train


def test_checkpoint_roundtrip(tmp_path):
    tree = {
        "a": jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
        "b": {"c": jnp.ones((2,), jnp.bfloat16), "d": jnp.int32(7)},
    }
    p = str(tmp_path / "x.ckpt")
    save_pytree(p, tree, step=3)
    restored = restore_pytree(p, jax.eval_shape(lambda: tree))
    for x, y in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_checkpoint_structure_validation(tmp_path):
    p = str(tmp_path / "x.ckpt")
    save_pytree(p, {"a": jnp.ones((2,))})
    with pytest.raises(KeyError):
        restore_pytree(p, {"b": jax.ShapeDtypeStruct((2,), jnp.float32)})
    with pytest.raises(ValueError):
        restore_pytree(p, {"a": jax.ShapeDtypeStruct((3,), jnp.float32)})


def test_checkpointer_retention(tmp_path):
    c = Checkpointer(str(tmp_path), keep=2)
    for s in (1, 2, 3, 4):
        c.save_async({"x": jnp.ones((4,)) * s}, s)
    c.wait()
    assert latest_step(str(tmp_path)) == 4
    import os

    assert not os.path.exists(ckpt_path(str(tmp_path), 1))


def test_train_smoke_and_loss_decreases():
    # fresh random batch per step: compare window means, not endpoints (the
    # per-batch loss noise is larger than 8 steps of learning signal)
    out = train("tinyllama-1.1b", "train_4k", steps=16, verbose=False)
    assert len(out["losses"]) == 16
    assert np.mean(out["losses"][-4:]) < np.mean(out["losses"][:4])


def test_crash_restart_resumes_identically(tmp_path):
    """Run 10 steps straight vs crash-at-7 + restart: same loss trajectory
    (deterministic data + checkpoint restore)."""
    d1 = str(tmp_path / "straight")
    ref = train("tinyllama-1.1b", "train_4k", steps=10, ckpt_dir=d1, ckpt_every=5, verbose=False)

    d2 = str(tmp_path / "crashy")
    with pytest.raises(RuntimeError):
        train("tinyllama-1.1b", "train_4k", steps=10, ckpt_dir=d2, ckpt_every=5,
              crash_at=7, verbose=False)
    assert latest_step(d2) == 5  # survived the crash
    out = train("tinyllama-1.1b", "train_4k", steps=10, ckpt_dir=d2, ckpt_every=5, verbose=False)
    # steps 5..9 replayed: final losses must agree
    np.testing.assert_allclose(out["losses"][-1], ref["losses"][-1], rtol=1e-5)


def test_train_other_families():
    # fresh random batches each step: assert stability, not convergence (the
    # fixed-batch learning tests live in test_archs_recsys / test_archs_gnn)
    out = train("deepfm", "train_batch", steps=5, verbose=False)
    assert np.isfinite(out["losses"]).all()
    out = train("pna", "full_graph_sm", steps=4, verbose=False)
    assert np.isfinite(out["losses"]).all()


def test_compressed_psum_error_feedback():
    from repro.dist.compression import compressed_psum

    n_dev = len(jax.devices())
    x = jnp.linspace(-1.0, 1.0, 64).reshape(8, 8)

    def f(x):
        mean, err = compressed_psum(x, "i", jnp.zeros_like(x))
        return mean, err

    from jax.sharding import AxisType, PartitionSpec as P

    mesh = jax.make_mesh((n_dev,), ("i",), axis_types=(AxisType.Auto,))
    g = jax.shard_map(
        f, mesh=mesh, in_specs=P(), out_specs=(P(), P()),
        check_vma=False,
    )
    mean, err = g(x)
    # single worker: mean == dequantized x; error = quantization residual
    np.testing.assert_allclose(np.asarray(mean), np.asarray(x), atol=0.02)
    assert float(jnp.abs(err).max()) < 0.02


def test_serve_batch_greedy():
    gen = serve_batch("tinyllama-1.1b", batch=2, prompt_len=8, gen_tokens=6, verbose=False)
    assert gen.shape == (2, 6)
    assert (gen >= 0).all()
