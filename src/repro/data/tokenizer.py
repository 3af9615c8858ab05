"""Hashing word tokenizer (nothing pretrained ships offline).

Deterministic: token id = (stable word hash) % (vocab - n_special) + n_special.
Good enough for LM training on synthetic corpora and for prompt length
accounting; reserves ids for special tokens and the yes/no answer tokens so
ModelOracle can read a stable logit position.

A word never spans whitespace, so text joined to other text at whitespace
tokenizes as its parts do: ModelOracle builds each prompt from the
instruction's ids, the row text's ids (``text_ids``, cached by content)
and the answer cue's ids.
"""
from __future__ import annotations

import collections
import hashlib
import re
import threading
from typing import List, Sequence, Tuple

import numpy as np

_WORD_RE = re.compile(r"[a-z0-9']+|[^\sa-z0-9']")

PAD, BOS, EOS, YES, NO = 0, 1, 2, 3, 4
N_SPECIAL = 8

# Bound of ``HashTokenizer.text_ids``'s cache, in ids (16 MiB of int32):
# ~16k texts of IMDB length, so a 1000-row table fits many times over,
# while a service over ever-new tables evicts its oldest texts.
TEXT_IDS_CACHE_IDS = 1 << 22


def _stable_hash(word: str) -> int:
    return int.from_bytes(hashlib.md5(word.encode()).digest()[:8], "little")


class HashTokenizer:
    def __init__(self, vocab_size: int = 32768):
        assert vocab_size > N_SPECIAL
        self.vocab_size = vocab_size
        # text -> read-only int32 ids, least recently used first
        self._text_ids: collections.OrderedDict = collections.OrderedDict()
        self._text_ids_n = 0   # ids held, at most TEXT_IDS_CACHE_IDS
        self._text_ids_lock = threading.Lock()

    def token_id(self, word: str) -> int:
        w = word.lower()
        if w == "yes":
            return YES
        if w == "no":
            return NO
        return _stable_hash(w) % (self.vocab_size - N_SPECIAL) + N_SPECIAL

    def encode(self, text: str, bos: bool = True) -> List[int]:
        ids = [self.token_id(w) for w in _WORD_RE.findall(text.lower())]
        return ([BOS] + ids) if bos else ids

    def text_ids(self, texts: Sequence[str]
                 ) -> Tuple[List[np.ndarray], int]:
        """Each text's ``encode(text, bos=False)`` as a read-only int32
        array, and how many texts were not cached.

        Cached by content, never by row, so a changed row simply misses;
        bounded by ``TEXT_IDS_CACHE_IDS`` ids with least-recently-used
        eviction.  Safe to call from several threads at once: misses are
        encoded outside the lock, and a text two threads encode at once
        gets equal ids from both."""
        out: list = [None] * len(texts)
        todo = []
        with self._text_ids_lock:
            for k, t in enumerate(texts):
                ids = self._text_ids.get(t)
                if ids is None:
                    todo.append(k)
                else:
                    self._text_ids.move_to_end(t)
                    out[k] = ids
        if not todo:
            return out, 0
        fresh: dict = {}
        for k in todo:
            t = texts[k]
            if t not in fresh:
                ids = np.array(self.encode(t, bos=False), np.int32)
                ids.setflags(write=False)
                fresh[t] = ids
            out[k] = fresh[t]
        with self._text_ids_lock:
            for t, ids in fresh.items():
                if t not in self._text_ids and len(ids) <= TEXT_IDS_CACHE_IDS:
                    self._text_ids[t] = ids
                    self._text_ids_n += len(ids)
            while self._text_ids_n > TEXT_IDS_CACHE_IDS:
                _, old = self._text_ids.popitem(last=False)
                self._text_ids_n -= len(old)
        return out, len(todo)

    def words(self, text: str) -> List[str]:
        return _WORD_RE.findall(text.lower())
