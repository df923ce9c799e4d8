"""Optimizer, checkpointing, and data-pipeline behaviour."""
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.tokens import MarkovTokens
from repro.train.checkpoint import load_checkpoint, save_checkpoint
from repro.train.optimizer import (adamw_init, adamw_update,
                                   clip_by_global_norm, cosine_schedule)


def test_adamw_minimizes_quadratic():
    params = {"w": jnp.array([5.0, -3.0])}
    opt = adamw_init(params)
    for _ in range(200):
        grads = {"w": 2.0 * params["w"]}
        params, opt = adamw_update(grads, opt, params, lr=0.1,
                                   weight_decay=0.0)
    assert float(jnp.abs(params["w"]).max()) < 0.05


def test_clip_by_global_norm():
    grads = {"a": jnp.full((4,), 10.0)}
    clipped, gn = clip_by_global_norm(grads, 1.0)
    assert abs(float(gn) - 20.0) < 1e-4
    norm = float(jnp.linalg.norm(clipped["a"]))
    assert abs(norm - 1.0) < 1e-4


def test_cosine_schedule_shape():
    lr = cosine_schedule(1e-3, warmup=10, total=100)
    assert float(lr(jnp.array(0))) == 0.0
    assert abs(float(lr(jnp.array(10))) - 1e-3) < 1e-9
    assert float(lr(jnp.array(100))) < 1e-4


def test_markov_tokens_learnable_and_bounded():
    data = MarkovTokens(512, effective=16, seed=0)
    b = next(data.batches(4, 32))
    assert b["tokens"].max() < 16 and b["tokens"].min() >= 0
    # labels are next tokens
    full = data.sample(2, 16)
    assert full.shape == (2, 17)


def test_checkpoint_roundtrip(tmp_path):
    params = {"w": jnp.arange(6.0).reshape(2, 3),
              "b": {"bias": jnp.full((3,), -1.5)}}
    opt = adamw_init(params)
    path = os.path.join(tmp_path, "ckpt.npz")
    save_checkpoint(path, params, opt, step=42)
    p2, o2, step = load_checkpoint(path, params, opt)
    assert step == 42
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(p2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree_util.tree_leaves(opt),
                    jax.tree_util.tree_leaves(o2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_compile_cache_dir_from_env_or_checkout(monkeypatch):
    """`enable_compile_cache` leaves an env-placed cache to JAX and sets
    nothing; without the variable the cache sits at the checkout's one
    fixed path."""
    from repro.launch import compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        path = compile_cache.enable_compile_cache()
        assert path == str(compile_cache.REPO_CACHE)
        assert jax.config.jax_compilation_cache_dir == path
        assert compile_cache.REPO_CACHE.parent.joinpath("src").is_dir()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
