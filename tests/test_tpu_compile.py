"""Compile the served path's kernels and full-width steps for a TPU v5e.

The TPU compiler ships with JAX and compiles for a chip that is described
but not attached, so these tests run on a CPU-only host: nothing executes,
and each test proves only that Mosaic and XLA accept the program at serving
shapes (what interpret-mode tests cannot show: block layouts, SMEM/VMEM
placement, device memory).  The topology is described inside a fixture —
never at import — so every pytest-xdist worker collects the same tests and
only the worker that runs this file loads the TPU library.

Code that asks ``jax.default_backend()`` still sees the CPU here; tests
that compile through the kernels' ``ops`` dispatch steer it to "tpu" with
monkeypatch.  The persistent compilation cache is off around these
compiles: entries written for a described chip cannot be read back.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core import clustering
from repro.embeddings.encoder import encoder_forward, init_encoder_params
from repro.kernels.decode_attention.kernel import decode_attention_pallas
from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.kmeans.kernel import assign_clusters_pallas
from repro.kernels.simvote.kernel import (simvote_scores_pallas,
                                          simvote_scores_segmented_pallas)
from repro.models import lm
from repro.kernels.flash_attention.ops import flash_min_seq

V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache as cc
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    cc.reset_cache()


@pytest.fixture
def on_tpu_dispatch(monkeypatch):
    """Make the kernels' ops wrappers take their TPU branch while tracing."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes)


@pytest.mark.parametrize("k", [4, 64])
def test_kmeans_assign_kernel_compiles(one_chip, k):
    c = _compile(assign_clusters_pallas, _spec(one_chip, (4000, 1024)),
                 _spec(one_chip, (k, 1024)))
    assert _has_kernel(c)


def test_kmeans_fit_compiles_with_kernel(one_chip, on_tpu_dispatch):
    """The Lloyd loop of a cold table's precluster, kernel inside."""
    key = jax.random.key_data(jax.random.key(0))
    c = _compile(lambda kd, x: clustering.kmeans(
        jax.random.wrap_key_data(kd), x, k=4),
        _spec(one_chip, key.shape, key.dtype),
        _spec(one_chip, (4001, 1024)))
    assert _has_kernel(c)


def test_simvote_kernel_compiles(one_chip):
    c = _compile(lambda x, s, y: simvote_scores_pallas(x, s, y, 0.5),
                 _spec(one_chip, (4000, 1024)), _spec(one_chip, (300, 1024)),
                 _spec(one_chip, (300,)))
    assert _has_kernel(c)


def test_simvote_segmented_kernel_compiles(one_chip):
    counts = np.array([1400, 1100, 900, 600])
    c = _compile(lambda x, s, y, t: simvote_scores_segmented_pallas(
        x, counts, s, y, t),
        _spec(one_chip, (int(counts.sum()), 1024)),
        _spec(one_chip, (4, 300, 1024)), _spec(one_chip, (4, 300)),
        _spec(one_chip, (4,)))
    assert _has_kernel(c)


@pytest.mark.parametrize("seq", [32, 256])
def test_flash_attention_kernel_compiles(one_chip, seq):
    qkv = _spec(one_chip, (64, 16, seq, 64), jnp.bfloat16)
    c = _compile(lambda q, k, v: flash_attention_pallas(q, k, v, causal=True),
                 qkv, qkv, qkv)
    assert _has_kernel(c)


@pytest.mark.parametrize("cache_len", [512, 300])
def test_decode_attention_kernel_compiles(one_chip, cache_len):
    kv = _spec(one_chip, (64, 16, cache_len, 64), jnp.bfloat16)
    c = _compile(decode_attention_pallas,
                 _spec(one_chip, (64, 16, 64), jnp.bfloat16), kv, kv,
                 _spec(one_chip, (64,), jnp.int32))
    assert _has_kernel(c)


def _select_step(cfg, sharding, batch, seq):
    params = jax.tree_util.tree_map(
        lambda a: _spec(sharding, a.shape, a.dtype), lm.abstract_params(cfg))
    return _compile(lambda p, t, n, ids: lm.first_logits_select(cfg, p, t, n,
                                                                ids),
                    params, _spec(sharding, (batch, seq), jnp.int32),
                    _spec(sharding, (batch,), jnp.int32),
                    _spec(sharding, (batch, 2), jnp.int32))


@pytest.mark.parametrize("attn_impl", ["auto", "flash"])
def test_oracle_select_step_compiles_full_width(one_chip, on_tpu_dispatch,
                                                attn_impl):
    """The engine's yes/no step at full qwen1.5-0.5b width, B=64: "flash"
    runs the kernel at every length, "auto" from head_dim 64's boundary up
    (the kernel at and above the boundary, none below it)."""
    cfg = get_config("qwen1.5-0.5b").replace(attn_impl=attn_impl)
    boundary = flash_min_seq(cfg.resolved_head_dim)
    for seq in (boundary // 2, boundary):
        c = _select_step(cfg, one_chip, 64, seq)
        assert _has_kernel(c) == (attn_impl == "flash"
                                  or seq >= boundary), seq
        assert _device_bytes(c) < V5E_HBM_BYTES


@pytest.mark.parametrize("seq", [128, 256, 512])
def test_oracle_select_step_compiles_7b_shape(one_chip, on_tpu_dispatch,
                                              seq):
    """codeqwen1.5-7b's step as its cell serves it (16 of 32 layers, GQA
    32/4 at head_dim 128, B=16, row lengths to the kernel) under "auto":
    the kernel from head_dim 128's boundary (256) up, none below it."""
    cfg = get_config("codeqwen1.5-7b").replace(n_layers=16)
    assert flash_min_seq(cfg.resolved_head_dim) == 256
    c = _select_step(cfg, one_chip, 16, seq)
    assert _has_kernel(c) == (seq >= 256)
    assert _device_bytes(c) < V5E_HBM_BYTES


def test_encoder_step_compiles_full_width(one_chip):
    """One e5-large embedding batch at full width, B=64, L=128."""
    cfg = get_config("e5-large")
    params = jax.tree_util.tree_map(
        lambda a: _spec(one_chip, a.shape, a.dtype),
        jax.eval_shape(lambda: init_encoder_params(cfg, jax.random.key(0))))
    c = _compile(lambda p, t, m: encoder_forward(cfg, p, t, m), params,
                 _spec(one_chip, (64, 128), jnp.int32),
                 _spec(one_chip, (64, 128), jnp.bool_))
    assert _device_bytes(c) < V5E_HBM_BYTES
