"""ModelOracle builds each prompt from cached parts: the per-tokenizer
text-id cache (``HashTokenizer.text_ids``, keyed by content) between the
oracle's prefix and suffix ids.  The parts must give exactly the ids of a
full ``encode`` of the prompt, and the cache must be shared, bounded and
safe across threads."""
import hashlib
import re
import sys
import threading

import numpy as np
import pytest

from repro.core.oracle import ModelOracle, evaluate_packed
from repro.data import make_dataset
from repro.data import tokenizer as tokenizer_mod
from repro.data.tokenizer import HashTokenizer
from repro.obs import MetricsRegistry, Tracer, use_tracer
from repro.serving import BucketBatcher

VOCAB = 151936

EDGE_TEXTS = [
    "",
    " ",
    "  leading and trailing  ",
    "\nnew\nlines\n",
    "\r\n\tcontrol whitespace\t",
    "'quoted'",
    "it's the director's cut'",
    "end with punctuation.",
    "...!?",
    "'",
    "ΟΔΟΣ",                 # Σ at a word end (final sigma)
    "Σ",
    "'Σ'",
    "ΑΣ'",
    "x\u0301\u03a3 y",      # a combining mark before a sigma
    "\u0301\u03a3",         # a text that opens with a combining mark
    "İstanbul İ",            # İ lowers to two code points
    "Straße ẞ naïve café",
    "emoji 🎉 at the end 🎉",
    "MiXeD CaSe WORDS",
]
PREDICATES = ["the review praises the strong acting", "ΟΔΟΣ'", "", "it's"]


def _reference_encode(text, vocab=VOCAB, bos=True):
    """The hash tokenizer's encode, written out independently."""
    ids = []
    for w in re.findall(r"[a-z0-9']+|[^\sa-z0-9']", text.lower()):
        w = w.lower()
        if w in ("yes", "no"):
            ids.append(3 if w == "yes" else 4)
        else:
            h = int.from_bytes(hashlib.md5(w.encode()).digest()[:8], "little")
            ids.append(h % (vocab - 8) + 8)
    return ([1] + ids) if bos else ids


def _full_ids(oracle, text):
    return oracle.tok.encode(f"{oracle.instruction}\ncondition: "
                             f"{oracle.predicate}\ntext: {text}\nanswer:")


class _StubEngine:
    """Records the prompts of each wave; answers "yes" for every row."""

    def __init__(self):
        self.batcher = BucketBatcher(max_batch=8)
        self.prompts = []

    def first_token_logits(self, prompts, token_ids=None):
        self.batcher.plan(prompts)
        self.prompts.extend(prompts)
        return np.tile(np.asarray([[1.0, 0.0]], np.float32),
                       (len(prompts), 1))


@pytest.fixture(scope="module")
def mix_texts():
    return make_dataset("imdb_review", n=200, seed=3).texts


def _counts(reg):
    snap = reg.snapshot()
    return (snap.get("oracle.text_ids_hits", 0),
            snap.get("oracle.text_ids_misses", 0))


# ------------------------------------------------------------- identity


@pytest.mark.parametrize("predicate", PREDICATES)
def test_pack_prompts_equal_full_encode(predicate, mix_texts):
    texts = list(mix_texts) + EDGE_TEXTS
    oracle = ModelOracle(_StubEngine(), HashTokenizer(VOCAB), predicate,
                         texts)
    ids = np.arange(len(texts))
    for i, p in zip(ids, oracle.pack_prompts(ids)):
        assert p.dtype == np.int32
        assert p.tolist() == _full_ids(oracle, texts[i]), repr(texts[i])
    # a second pass reads the cache and builds the same prompts
    for i, p in zip(ids, oracle.pack_prompts(ids)):
        assert p.tolist() == _full_ids(oracle, texts[i]), repr(texts[i])


def test_input_tokens_equal_full_encode(mix_texts):
    texts = list(mix_texts[:40]) + EDGE_TEXTS
    tok = HashTokenizer(VOCAB)
    engine = _StubEngine()
    direct = ModelOracle(engine, tok, "mentions the plot", texts)
    packed = ModelOracle(engine, tok, "doubts the ending", texts)
    ids = np.arange(len(texts))
    want = {o: sum(len(_full_ids(o, t)) for t in texts)
            for o in (direct, packed)}
    assert direct._tokens_of(ids) == want[direct]
    assert direct(ids).all()
    assert direct.stats.input_tokens == want[direct]
    (out,), info = evaluate_packed([(packed, ids)])
    assert out.all()
    assert packed.stats.input_tokens == want[packed]
    assert info["tokens"] == want[packed] + len(ids)
    # what the engine was handed, in either path, is the full encode
    assert [p.tolist() for p in engine.prompts] == (
        [_full_ids(direct, t) for t in texts]
        + [_full_ids(packed, t) for t in texts])


# --------------------------------------------------- sharing and safety


def test_second_oracle_over_same_texts_only_hits(mix_texts):
    texts = list(mix_texts)
    tok = HashTokenizer(VOCAB)
    ids = np.arange(len(texts))
    reg = MetricsRegistry()
    with use_tracer(Tracer(metrics=reg)):
        ModelOracle(_StubEngine(), tok, "praises the cast", texts
                    ).pack_prompts(ids)
        hits0, misses0 = _counts(reg)
        assert hits0 + misses0 == len(ids)
        assert misses0 == len(set(texts))
        second = ModelOracle(_StubEngine(), tok, "criticizes the score",
                             texts)
        for i, p in zip(ids, second.pack_prompts(ids)):
            assert p.tolist() == _full_ids(second, texts[i])
    assert _counts(reg) == (hits0 + len(ids), misses0)


def test_changed_row_gets_new_text_ids(mix_texts):
    texts = list(mix_texts[:10])
    oracle = ModelOracle(_StubEngine(), HashTokenizer(VOCAB),
                         "mentions the acting", texts)
    old = oracle.pack_prompts([3])[0].tolist()
    texts[3] = "An entirely different review, rewritten in place."
    new = oracle.pack_prompts([3])[0].tolist()
    assert new != old
    assert new == _full_ids(oracle, texts[3])
    assert oracle._tokens_of(np.asarray([3])) == len(new)


def test_cache_stays_within_bound(monkeypatch, mix_texts):
    bound = 400
    monkeypatch.setattr(tokenizer_mod, "TEXT_IDS_CACHE_IDS", bound)
    tok = HashTokenizer(VOCAB)
    texts = list(dict.fromkeys(mix_texts))
    keep = texts[0]
    tok.text_ids([keep])
    for k in range(1, len(texts)):
        got, misses = tok.text_ids([keep, texts[k]])
        assert misses == 1                        # keep stays most recent
        assert [g.tolist() for g in got] == [
            _reference_encode(t, bos=False) for t in (keep, texts[k])]
        assert tok._text_ids_n <= bound
        assert tok._text_ids_n == sum(len(v) for v in tok._text_ids.values())
    assert keep in tok._text_ids                  # least recently used go
    assert texts[1] not in tok._text_ids
    # a text longer than the bound is encoded, and not cached
    long_text = " ".join(texts)
    (ids,), misses = tok.text_ids([long_text])
    assert misses == 1 and len(ids) > bound
    assert ids.tolist() == _reference_encode(long_text, bos=False)
    assert long_text not in tok._text_ids
    assert tok._text_ids_n <= bound


def test_cached_ids_are_read_only(mix_texts):
    tok = HashTokenizer(VOCAB)
    (ids,), _ = tok.text_ids([mix_texts[0]])
    with pytest.raises(ValueError):
        ids[0] = 0
    oracle = ModelOracle(_StubEngine(), tok, "mentions the plot", mix_texts)
    prompt = oracle.pack_prompts([0])[0]
    prompt[:] = 0                                 # a prompt is its own copy
    assert oracle.pack_prompts([0])[0].tolist() == _full_ids(
        oracle, mix_texts[0])


def test_concurrent_tokenizing_gets_identical_ids(monkeypatch, mix_texts):
    # a bound below the texts' ids keeps threads evicting, and inserting
    # the same texts, while others read
    bound = 150
    monkeypatch.setattr(tokenizer_mod, "TEXT_IDS_CACHE_IDS", bound)
    tok = HashTokenizer(VOCAB)
    texts = list(mix_texts[:30]) + EDGE_TEXTS
    want = [_reference_encode(t, bos=False) for t in texts]
    errors, n_threads = [], 16

    def work(k):
        try:
            rng = np.random.default_rng(k)
            for _ in range(400):
                pick = rng.choice(len(texts), 8)
                got, _ = tok.text_ids([texts[i] for i in pick])
                for i, g in zip(pick, got):
                    if g.tolist() != want[i]:
                        errors.append((k, int(i)))
        except Exception as e:  # surfaced by the assertion below
            errors.append(repr(e))

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work, args=(k,))
                   for k in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert errors == []
    assert tok._text_ids_n <= bound
    assert tok._text_ids_n == sum(len(v) for v in tok._text_ids.values())


@pytest.mark.parametrize("bos", [True, False])
def test_encode_unchanged_and_uncached(bos, mix_texts):
    tok = HashTokenizer(VOCAB)
    for t in list(mix_texts) + EDGE_TEXTS:
        got = tok.encode(t, bos=bos)
        assert type(got) is list
        assert got == _reference_encode(t, bos=bos)
    assert len(tok._text_ids) == 0 and tok._text_ids_n == 0
