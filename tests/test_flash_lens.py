"""The length-aware flash kernel on the served prefill path.

The kernel runs in interpret mode, so the tile schedule the chip runs (which
tiles compute, which K/V blocks are fetched, what padding rows hold) is
checked here against the plain path through ``first_logits_select`` on
right-padded batches, and ``attn_impl="auto"``'s routing is checked for each
case that must keep its old path.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.distributed.api import sharding_context
from repro.kernels.flash_attention.kernel import (flash_attention_pallas,
                                                  tile_counts)
from repro.kernels.flash_attention.ops import flash_blocks, flash_min_seq
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.models import layers, lm
from repro.obs import MetricsRegistry, Tracer, use_tracer
from repro.serving import ServingEngine

# smoke widths at the served head shapes: (arch, heads, kv heads, head_dim)
SHAPES = {"g1-hd64": ("qwen1.5-0.5b", 2, 2, 64),
          "g8-hd128": ("codeqwen1.5-7b", 8, 1, 128)}


def _cfg(shape, attn_impl="plain"):
    arch, h, kv, hd = SHAPES[shape]
    return smoke_config(arch).replace(n_heads=h, n_kv_heads=kv, head_dim=hd,
                                      attn_impl=attn_impl)


def _padded_batch(vocab, L, lens, seed=0):
    rng = np.random.default_rng(seed)
    toks = np.zeros((len(lens), L), np.int32)
    for r, n in enumerate(lens):
        toks[r, :n] = rng.integers(1, vocab, n)
    return toks, np.asarray(lens, np.int32)


# --------------------------------------------- parity through the model


def _tile(cfg):
    return flash_blocks(cfg.resolved_head_dim, cfg.n_heads,
                        cfg.n_kv_heads)["block_q"]


@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("case", ["one-tile", "several-tiles", "tile-edges"])
@pytest.mark.parametrize("impl", ["flash", "flash-ref"])
def test_first_logits_select_matches_plain(shape, case, impl):
    cfg = _cfg(shape)
    t = _tile(cfg)
    L, lens = {
        # a bucket below the tile: a single token, a ragged row, full
        "one-tile": (64, [1, 37, 64]),
        # 2 x 2 tiles: the same three kinds
        "several-tiles": (2 * t, [1, t - 51, 2 * t]),
        # every row crosses a tile edge
        "tile-edges": (2 * t, [t + 2, t + 72, t + 1]),
    }[case]
    params = lm.init_params(cfg, jax.random.key(1))
    toks, n = _padded_batch(cfg.vocab_size, L, lens)
    ids = jnp.asarray([5, 9], jnp.int32)
    ref = lm.first_logits_select(cfg, params, toks, n, ids)
    got = lm.first_logits_select(cfg.replace(attn_impl=impl), params, toks,
                                 n, ids)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


# ------------------------------------------------ padding and skipping


def _qkv(shape, B, S, seed=0):
    _, h, kv, hd = SHAPES[shape]
    ks = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(ks[0], (B, h, S, hd), jnp.float32),
            jax.random.normal(ks[1], (B, kv, S, hd), jnp.float32),
            jax.random.normal(ks[2], (B, kv, S, hd), jnp.float32))


@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("window", [None, 100])
def test_padding_rows_finite_and_zero(shape, window):
    """Rows at or past a row's length come out finite (zero), since they
    feed the next layer's K/V at padding positions; real rows match the
    reference, with a sliding window too."""
    q, k, v = _qkv(shape, 3, 256)
    lens = jnp.asarray([1, 77, 256], jnp.int32)
    out = np.asarray(flash_attention_pallas(q, k, v, lens, window=window,
                                            block_q=64, block_k=64,
                                            interpret=True))
    assert np.isfinite(out).all()
    ref = np.asarray(flash_attention_ref(q, k, v, lens, window=window))
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)
    for r, n in enumerate(np.asarray(lens)):
        assert not out[r, :, n:].any()


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_tiles_past_length_are_not_read(shape):
    """K/V (and query) tiles wholly past a row's length hold NaN: the kernel
    neither fetches nor computes them, so every real row is unchanged."""
    q, k, v = _qkv(shape, 2, 256)
    lens = jnp.asarray([100, 128], jnp.int32)   # tile 128..255 all padding
    clean = np.asarray(flash_attention_pallas(q, k, v, lens, block_q=64,
                                              block_k=64, interpret=True))
    past = (jnp.arange(256) >= 128)[None, None, :, None]
    q, k, v = (jnp.where(past, jnp.nan, a) for a in (q, k, v))
    dirty = np.asarray(flash_attention_pallas(q, k, v, lens, block_q=64,
                                              block_k=64, interpret=True))
    assert np.array_equal(dirty, clean)


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_nan_padding_never_reaches_selected_logits(shape):
    """Through the model: a NaN padding embedding makes every K/V tile past
    a row's length NaN.  Plain attention reads them (0 x NaN in the PV
    product) and its logits turn NaN; the kernel's do not change."""
    cfg = _cfg(shape)
    params = lm.init_params(cfg, jax.random.key(2))
    t = _tile(cfg)        # rows of one and two whole tiles
    toks, n = _padded_batch(cfg.vocab_size, 2 * t, [t, 2 * t])
    ids = jnp.asarray([5, 9], jnp.int32)
    flash = cfg.replace(attn_impl="flash")
    clean = np.asarray(lm.first_logits_select(flash, params, toks, n, ids))
    table = params["embed"]["table"]
    nan_pad = dict(params, embed={"table": table.at[0].set(jnp.nan)})
    dirty = np.asarray(lm.first_logits_select(flash, nan_pad, toks, n, ids))
    assert np.array_equal(dirty, clean)
    plain = np.asarray(lm.first_logits_select(cfg, nan_pad, toks, n, ids))
    assert np.isnan(plain[0]).all()


def test_tile_counts():
    # rows of 1, 77 and 128 tokens in 32-tiles of a 128 bucket: 1 + (1+2+3)
    # + 4*5/2 tiles of the 3 x 16 grid
    assert tile_counts([1, 77, 128], 128, block_q=32, block_k=32) == (17, 48)
    # a bucket below the tile is one tile a row
    assert tile_counts([3, 20], 32, block_q=128, block_k=128) == (2, 2)


# ------------------------------------------------------------- routing


@pytest.fixture
def on_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _auto(shape):
    return _cfg(shape, "auto")


def _boundary(shape):
    return flash_min_seq(SHAPES[shape][3])


@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("times", [1, 4])
def test_auto_takes_the_kernel_on_tpu_from_the_boundary(on_tpu, shape,
                                                        times):
    assert layers.attention_path(_auto(shape),
                                 times * _boundary(shape)) == "flash"


@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("case,S,kw,old", [
    ("below-boundary", None, {}, "plain"),
    ("window", 512, {"window": 64}, "chunked"),   # the banded schedule
    ("non-causal", 512, {"causal": False}, "plain"),
    ("cross-attention", 512, {"cross": True}, "plain"),
    ("off-tile", 384 + 32, {}, "plain"),
])
def test_auto_keeps_its_old_path_on_tpu(on_tpu, shape, case, S, kw, old):
    S = _boundary(shape) // 2 if S is None else S
    assert layers.attention_path(_auto(shape), S, **kw) == old


@pytest.mark.parametrize("head_dim", [16, 160, 256])
def test_auto_keeps_plain_at_a_head_dim_never_timed(on_tpu, head_dim):
    """Only a head_dim with a measured boundary takes the kernel."""
    cfg = _auto("g1-hd64").replace(head_dim=head_dim)
    assert flash_min_seq(head_dim) is None
    assert layers.attention_path(cfg, 2048) == "plain"


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_auto_keeps_plain_under_a_sharding_context(on_tpu, shape):
    with sharding_context(object()):
        assert layers.attention_path(_auto(shape), 512) == "plain"


def test_auto_on_cpu_stays_plain_and_matches_it():
    auto = _auto("g1-hd64")
    assert layers.attention_path(auto, 512) == "plain"
    params = lm.init_params(auto, jax.random.key(3))
    toks, n = _padded_batch(auto.vocab_size, 512, [10, 512])
    ids = jnp.asarray([5, 9], jnp.int32)
    a = lm.first_logits_select(auto, params, toks, n, ids)
    p = lm.first_logits_select(auto.replace(attn_impl="plain"), params,
                               toks, n, ids)
    assert np.array_equal(np.asarray(a), np.asarray(p))


# -------------------------------------------------- engine accounting


@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("impl,path", [("plain", "plain"),
                                       ("flash", "flash")])
def test_engine_reports_attn_path_and_tiles(shape, impl, path):
    cfg = _cfg(shape, impl)
    params = lm.init_params(cfg, jax.random.key(4))
    t = _tile(cfg)
    lens = (3, t + 12, 2 * t - 6, 2 * t)   # one bucket of 2 x 2 tiles a row
    rng = np.random.default_rng(0)
    prompts = [list(rng.integers(1, cfg.vocab_size, n)) for n in lens]
    tr = Tracer(metrics=MetricsRegistry())
    with use_tracer(tr):
        ServingEngine(cfg, params, max_batch=4).first_token_logits(
            prompts, token_ids=[5, 9])
    ticks = [s for s in tr.spans() if s.name == "engine_tick"]
    assert [s.attrs["attn_path"] for s in ticks] == [path]
    snap = tr.metrics.snapshot()
    run, full = snap["engine.attn_tiles_run"], snap["engine.attn_tiles_full"]
    # the kernel computes 1 + 3 + 3 + 3 of the 16 tiles
    assert full == 16
    assert run == (10 if impl == "flash" else 16)
