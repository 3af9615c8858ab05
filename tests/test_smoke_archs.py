"""Per-architecture smoke tests: reduced same-family config, one forward and
one train step on CPU; asserts output shapes and no NaNs (assignment f)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config, smoke_config, list_archs
from repro.models import lm
from repro.train import OptConfig, adamw_init, make_train_step

ASSIGNED = ["falcon-mamba-7b", "mixtral-8x22b", "dbrx-132b", "internvl2-26b",
            "gemma3-12b", "stablelm-12b", "codeqwen1.5-7b", "qwen1.5-0.5b",
            "jamba-v0.1-52b", "whisper-base"]


def _batch_for(cfg, B=2, S=32, key=0):
    k = jax.random.key(key)
    batch = {"tokens": jax.random.randint(k, (B, S), 0, cfg.vocab_size),
             "targets": jax.random.randint(k, (B, S), 0, cfg.vocab_size)}
    if cfg.num_prefix_embeds:
        batch["prefix_embeds"] = jax.random.normal(
            k, (B, cfg.num_prefix_embeds, cfg.d_model), jnp.float32)
    if cfg.is_encdec:
        batch["enc_frames"] = jax.random.normal(
            k, (B, cfg.encoder_len, cfg.d_model), jnp.float32)
    return batch


@pytest.mark.parametrize("arch", ASSIGNED)
def test_smoke_forward(arch):
    cfg = smoke_config(arch)
    params = lm.init_params(cfg, jax.random.key(0))
    batch = _batch_for(cfg)
    logits, aux = lm.forward(cfg, params, batch["tokens"],
                             prefix_embeds=batch.get("prefix_embeds"),
                             enc_frames=batch.get("enc_frames"))
    B, S = batch["tokens"].shape
    assert logits.shape == (B, S + cfg.num_prefix_embeds, cfg.padded_vocab)
    assert not bool(jnp.isnan(logits).any()), arch
    assert not bool(jnp.isnan(aux)), arch


@pytest.mark.parametrize("arch", ASSIGNED)
def test_smoke_train_step(arch):
    cfg = smoke_config(arch)
    params = lm.init_params(cfg, jax.random.key(0))
    oc = OptConfig(lr=1e-3, warmup_steps=1, total_steps=10)
    opt = adamw_init(params, oc)
    step = make_train_step(cfg, oc)
    batch = _batch_for(cfg)
    params2, opt2, metrics = step(params, opt, batch)
    assert np.isfinite(float(metrics["loss"])), arch
    assert np.isfinite(float(metrics["grad_norm"])), arch
    # parameters actually moved
    l0 = jax.tree_util.tree_leaves(params)[0]
    l1 = jax.tree_util.tree_leaves(params2)[0]
    assert not np.allclose(np.asarray(l0), np.asarray(l1)), arch


def test_full_configs_match_assignment():
    """Exact dims from the assignment table."""
    expect = {
        "falcon-mamba-7b": (64, 4096, 65024),
        "mixtral-8x22b": (56, 6144, 32768),
        "dbrx-132b": (40, 6144, 100352),
        "internvl2-26b": (48, 6144, 92553),
        "gemma3-12b": (48, 3840, 262144),
        "stablelm-12b": (40, 5120, 100352),
        "codeqwen1.5-7b": (32, 4096, 92416),
        "qwen1.5-0.5b": (24, 1024, 151936),
        "jamba-v0.1-52b": (32, 4096, 65536),
        "whisper-base": (6, 512, 51865),
    }
    for arch, (L, D, V) in expect.items():
        cfg = get_config(arch)
        assert cfg.n_layers == L and cfg.d_model == D and cfg.vocab_size == V


def test_family_features():
    assert all(s.kind == "mamba" for s in get_config("falcon-mamba-7b").pattern)
    assert get_config("mixtral-8x22b").pattern[0].window == 4096
    assert get_config("dbrx-132b").n_experts == 16
    assert get_config("dbrx-132b").top_k == 4
    jam = get_config("jamba-v0.1-52b")
    kinds = [s.kind for s in jam.pattern]
    assert kinds.count("attn") == 1 and kinds.count("mamba") == 7
    assert sum(s.ffn == "moe" for s in jam.pattern) == 4
    gem = get_config("gemma3-12b")
    wins = [s.window for s in gem.pattern]
    assert wins.count(1024) == 5 and wins.count(None) == 1
    assert gem.resolved_head_dim == 256
    assert get_config("codeqwen1.5-7b").qkv_bias
    assert get_config("whisper-base").is_encdec


def test_codeqwen_preset_matches_benchmark_config():
    """The preset and the chip benchmark's file describe one model: every
    width, the KV heads, the tie, epsilon and theta; the file cuts depth
    alone and states the published value."""
    import json
    import pathlib
    path = (pathlib.Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
            / "configs" / "codeqwen1.5-7b.json")
    c = json.loads(path.read_text())
    cfg = get_config("codeqwen1.5-7b")
    assert c["reduced"] == ["num_hidden_layers"]
    assert cfg.n_layers == c["published"]["num_hidden_layers"]
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_ff, cfg.vocab_size) == (
        c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"],
        c["intermediate_size"], c["vocab_size"])
    assert cfg.resolved_head_dim == c["hidden_size"] // c["num_attention_heads"] == 128
    assert cfg.tie_embeddings == c["tie_word_embeddings"] is False
    assert cfg.qkv_bias == c["attention_bias"] is True
    assert cfg.norm_eps == c["rms_norm_eps"] and cfg.rope_theta == c["rope_theta"]
    assert cfg.dtype == c["torch_dtype"]
    assert 7.2e9 < cfg.param_count() < 7.3e9
    smoke = smoke_config("codeqwen1.5-7b")
    assert smoke.n_kv_heads < smoke.n_heads and not smoke.tie_embeddings
