"""The served yes/no logits of the 7B configuration's shape against the
plain reference, on the CPU at the configuration's smoke size: grouped
query attention (8 query heads over 2 KV heads) and an untied head, the
two things the 0.5B configuration does not exercise.

``ServingEngine.first_token_logits`` serves prompts of mixed lengths in
one packed wave (buckets 32 to 256, batches cut at ``max_batch``);
``reference.oracle_logits`` computes the same prompts on the same seeded
weights with K/V repeated per query head, in float32 at HIGHEST.  Two
faults planted where the program produces the logits must each fail the
comparison: query heads grouped onto KV heads as ``h % KV`` instead of
``h // G``, and the head read from the embedding table instead of
``lm_head``.
"""
import pathlib
import sys

_BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(_BENCH), str(_BENCH.parents[1] / "src")]

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from chipbench import cells, harness, reference, weights  # noqa: E402

# Tolerance on the largest absolute difference of a yes/no logit, whose
# magnitude here is up to ~3.  float32: both sides compute in float32 and
# differ only in the order of summation (the grouped einsum against
# repeated K/V, padding to the bucket), ~1e-6 measured.  bfloat16, as the
# configuration states: the program rounds activations to bfloat16 (2^-8
# relative) some ten times a layer, the reference not at all; 0.035 is
# measured.  Either planted fault moves a logit by more than 1.
TOLERANCE = {"float32": 1e-4, "bfloat16": 0.1}
LENS = [3, 7, 17, 31, 33, 40, 50, 64, 65, 90, 100, 129, 200, 250]
SEED = 2 ** 35 + 11


def _config(dtype):
    c = cells.load_cell("cq7b-profiles", smoke=True).config
    assert c["num_key_value_heads"] < c["num_attention_heads"]
    assert not c["tie_word_embeddings"]
    return dict(c, torch_dtype=dtype)


def _prompts(c):
    rng = np.random.default_rng(SEED)
    return [[reference.BOS] + rng.integers(reference.N_SPECIAL, c["vocab_size"],
                                           n - 1).tolist() for n in LENS]


def _served(c, params, prompts, cfg=None):
    from repro.serving import ServingEngine
    engine = ServingEngine(cfg or harness.oracle_model_config(c), params,
                           max_batch=c["serving"]["max_batch"])
    out = engine.first_token_logits(prompts, token_ids=[reference.YES, reference.NO])
    assert engine.stats["batches"] > 1      # several buckets in the one wave
    return out


@pytest.fixture(scope="module", params=sorted(TOLERANCE))
def case(request):
    c = _config(request.param)
    params = weights.oracle_params(c, SEED)
    prompts = _prompts(c)
    return c, params, prompts, reference.oracle_logits(c, params, prompts), \
        TOLERANCE[request.param]


def test_served_logits_match_reference(case):
    c, params, prompts, ref, tol = case
    got = _served(c, params, prompts)
    assert got.shape == ref.shape == (len(LENS), 2)
    assert np.abs(got - ref).max() <= tol


def _grouped_as_modulo(cfg, p, x, kv_x=None):
    """_project_qkv with query head h read against KV head h % KV."""
    B, S, _ = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    q, k, v = x @ p["wq"], x @ p["wk"], x @ p["wv"]
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, S, H // KV, KV, hd).swapaxes(2, 3)
    return q, k.reshape(B, S, KV, hd), v.reshape(B, S, KV, hd)


def test_kv_heads_mapped_modulo_fail(case, monkeypatch):
    from repro.models import layers
    c, params, prompts, ref, tol = case
    monkeypatch.setattr(layers, "_project_qkv", _grouped_as_modulo)
    assert np.abs(_served(c, params, prompts) - ref).max() > 10 * tol


def test_head_read_from_embedding_fails(case):
    c, params, prompts, ref, tol = case
    tied = harness.oracle_model_config(c).replace(tie_embeddings=True)
    assert "lm_head" in params
    assert np.abs(_served(c, params, prompts, tied) - ref).max() > 10 * tol
