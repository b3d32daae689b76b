"""The hashing tokenizer's word-to-id map and its one-pass batch (PR 33).

``encode_batch`` must return, element for element, dtype and shape included,
what the definition gives. The definition is written out here
(``definition``), from the standard library alone, and imports nothing from
the code under test: a word's id is its blake2b digest of 8 bytes read
little-endian, folded into the vocabulary above the two reserved ids; a row
is CLS and the words' ids, cut to ``max_len``; the batch is padded to the
ladder rung of its longest row."""

from __future__ import annotations

import hashlib
import inspect
import re
import struct
import sys
import threading

import numpy as np
import pytest

from pathway_tpu.xpacks.llm import _tokenizer
from pathway_tpu.xpacks.llm._tokenizer import HashingTokenizer

MAX_LEN = 32
WORDS = re.compile(r"[a-zA-Z]+|\d+|[^\sa-zA-Z\d]", re.UNICODE)


def word_id(word: str, vocab_size: int) -> int:
    h = struct.unpack("<Q", hashlib.blake2b(word.encode(), digest_size=8).digest())[0]
    return 2 + h % (vocab_size - 2)


def definition(texts, max_len, vocab_size=30522, lowercase=True):
    rows = []
    for text in texts:
        words = WORDS.findall(text.lower() if lowercase else text)
        rows.append(([1] + [word_id(w, vocab_size) for w in words])[:max_len])
    longest = max((len(r) for r in rows), default=1)
    rung = 16
    while rung < longest:
        rung *= 2
    rung = min(rung, max_len)
    ids = np.zeros((len(rows), rung), dtype=np.int32)
    mask = np.zeros((len(rows), rung), dtype=np.float32)
    for i, row in enumerate(rows):
        ids[i, : len(row)] = row
        mask[i, : len(row)] = 1.0
    return ids, mask


def same(got, want):
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(g, w)


def numbered(n, start=0):
    """n distinct words of letters (the digits of a number as letters)."""
    return ["w" + "".join("abcdefghij"[int(d)] for d in str(start + i)) for i in range(n)]


TEXTS = {
    "empty": "",
    "spaces": "  \t \n ",
    "punctuation": "?!... --- (;:) [] {} ''",
    "digits": "1234567890 42 007 3.14159",
    "mixed_case": "Hello hELLo HELLO World wORLD",
    "non_ascii": "naïve café – Ελληνικά кириллица 日本語 ±∞ € ß İ",
    "one_token": "word",
    "one_symbol": "#",
    "identifier": "x86_64-linux-gnu/a1b2c3d4e5f6",
    "repeats": "the cat and the dog and the bird and the cat",
    "max_len_less_2": " ".join(numbered(MAX_LEN - 2)),
    "max_len_less_1": " ".join(numbered(MAX_LEN - 1)),
    "max_len": " ".join(numbered(MAX_LEN)),
    "max_len_plus_1": " ".join(numbered(MAX_LEN + 1)),
    "much_longer": " ".join(numbered(5 * MAX_LEN)),
}


def batch_of(n, seed=0):
    """n texts of 0..80 words over a small vocabulary, case, digits, symbols
    and non-ASCII letters mixed in: some shorter than a rung, some cut."""
    rng = np.random.default_rng(seed)
    vocab = numbered(400) + ["Hello", "WORLD", "42", "2026", "é", "日", "?", "--", "x_y", "naïve"]
    texts = []
    for _ in range(n):
        count = int(rng.integers(0, 81))
        texts.append(" ".join(vocab[int(j)] for j in rng.integers(0, len(vocab), count)))
    return texts


@pytest.mark.parametrize("max_len", [MAX_LEN, 16, 512])
@pytest.mark.parametrize("name", sorted(TEXTS))
def test_a_text_alone_reads_as_the_definition(name, max_len):
    same(HashingTokenizer().encode_batch([TEXTS[name]], max_len), definition([TEXTS[name]], max_len))


@pytest.mark.parametrize("max_len", [MAX_LEN, 16, 512, 1, 0])
def test_all_the_texts_together_read_as_the_definition(max_len):
    texts = list(TEXTS.values())
    same(HashingTokenizer().encode_batch(texts, max_len), definition(texts, max_len))


def test_truncation_keeps_cls_and_the_first_words():
    words = numbered(MAX_LEN + 5)
    ids, mask = HashingTokenizer().encode_batch([" ".join(words)], MAX_LEN)
    assert ids.shape == (1, MAX_LEN) and mask.all()
    assert ids[0].tolist() == [1] + [word_id(w, 30522) for w in words[: MAX_LEN - 1]]


@pytest.mark.parametrize("max_len", [64, 256])
@pytest.mark.parametrize("n", [0, 1, 2, 33, 300])
def test_a_batch_reads_as_the_definition(n, max_len):
    texts = batch_of(n, seed=n)
    same(HashingTokenizer().encode_batch(texts, max_len), definition(texts, max_len))


@pytest.mark.parametrize("vocab_size, lowercase", [(1000, True), (131072, True), (30522, False), (3, True)])
def test_the_constructors_arguments_read_as_the_definition(vocab_size, lowercase):
    texts = batch_of(33, seed=7)
    tok = HashingTokenizer(vocab_size=vocab_size, lowercase=lowercase)
    same(tok.encode_batch(texts, 64), definition(texts, 64, vocab_size, lowercase))


@pytest.mark.parametrize("n", [1, 33, 300])
def test_all_misses_then_all_hits_give_the_same_arrays(n):
    texts = batch_of(n, seed=100 + n)
    tok = HashingTokenizer()
    first = tok.encode_batch(texts, 64)
    words, hits = tok.words, tok.word_hits
    second = tok.encode_batch(texts, 64)
    same(first, second)
    same(second, definition(texts, 64))
    assert words == int(first[1].sum()) - n  # every real position but the CLS
    assert tok.words == 2 * words and tok.word_hits - hits == words  # the second call only read


def test_distinct_words_all_miss_and_come_back_as_hits():
    text = " ".join(numbered(50))
    tok = HashingTokenizer()
    tok.encode_batch([text], 64)
    assert (tok.words, tok.word_hits) == (50, 0) and len(tok._ids) == 50
    tok.encode_batch([text, text], 64)
    assert (tok.words, tok.word_hits) == (150, 100) and len(tok._ids) == 50


def test_a_word_missed_twice_in_one_batch_is_hashed_once(monkeypatch):
    tok = HashingTokenizer()
    hashed = []
    plain = tok._hash
    monkeypatch.setattr(tok, "_hash", lambda word: hashed.append(word) or plain(word))
    texts = ["again and again and again", "again"]
    same(tok.encode_batch(texts, 16), definition(texts, 16))
    assert sorted(hashed) == ["again", "and"]
    assert (tok.words, tok.word_hits) == (6, 0)  # a hit is a word the map held before the call


@pytest.mark.parametrize("bound", [0, 1, 4, 64])
def test_the_map_never_outgrows_its_bound(monkeypatch, bound):
    monkeypatch.setattr(_tokenizer, "_MAP_ENTRIES", bound)
    tok = HashingTokenizer()
    for round_ in range(4):
        for n in (1, 2, 33):
            texts = batch_of(n, seed=round_ * 10 + n)
            same(tok.encode_batch(texts, 64), definition(texts, 64))
            assert len(tok._ids) <= bound
        assert tok.encode("alpha beta gamma delta epsilon zeta", 64) == definition(
            ["alpha beta gamma delta epsilon zeta"], 64
        )[0][0, :7].tolist()
        assert len(tok._ids) <= bound
    assert 0 <= tok.word_hits <= tok.words
    if bound == 0:  # with no room nothing is ever answered from the map
        assert tok.word_hits == 0


def test_at_the_bound_the_map_starts_afresh(monkeypatch):
    monkeypatch.setattr(_tokenizer, "_MAP_ENTRIES", 4)
    tok = HashingTokenizer()
    tok.encode_batch(["one two three"], 16)
    assert set(tok._ids) == {"one", "two", "three"}
    tok.encode_batch(["three four"], 16)  # fits: 4 entries
    assert set(tok._ids) == {"one", "two", "three", "four"}
    tok.encode_batch(["five one"], 16)  # does not: the old entries go, the new word stays
    assert set(tok._ids) == {"five"}
    same(tok.encode_batch(["one two three four five six"], 16), definition(["one two three four five six"], 16))
    assert len(tok._ids) <= 4


def test_the_bound_is_a_constant_of_the_module():
    assert 100_000 <= _tokenizer._MAP_ENTRIES <= 1_000_000 and 16 <= _tokenizer._MAP_WORD_CHARS <= 64
    assert list(inspect.signature(HashingTokenizer.__init__).parameters) == ["self", "vocab_size", "lowercase"]
    assert list(inspect.signature(HashingTokenizer.encode_batch).parameters) == ["self", "texts", "max_len"]


def test_a_word_over_the_length_cap_is_hashed_and_not_stored():
    cap = _tokenizer._MAP_WORD_CHARS
    at_cap, over = "a" * cap, "b" * (cap + 1)
    texts = [f"{over} short {at_cap} {over}", "9" * (cap + 1)]
    tok = HashingTokenizer()
    same(tok.encode_batch(texts, 16), definition(texts, 16))
    assert set(tok._ids) == {"short", at_cap}
    same(tok.encode_batch(texts, 16), definition(texts, 16))
    assert set(tok._ids) == {"short", at_cap}
    assert (tok.words, tok.word_hits) == (10, 2)  # the long ones miss every time


def test_two_vocabularies_do_not_see_each_others_ids():
    texts = batch_of(33, seed=3)
    small, large = HashingTokenizer(vocab_size=1000), HashingTokenizer(vocab_size=131072)
    for _ in range(2):  # misses, then hits, interleaved
        same(small.encode_batch(texts, 64), definition(texts, 64, 1000))
        same(large.encode_batch(texts, 64), definition(texts, 64, 131072))
    assert small._ids is not large._ids and small._ids.keys() == large._ids.keys()
    assert max(small._ids.values()) < 1000 < max(large._ids.values())


@pytest.mark.parametrize("name", sorted(TEXTS))
def test_encode_is_a_row_of_the_batch(name):
    tok = HashingTokenizer()
    for _ in range(2):
        row = tok.encode(TEXTS[name], MAX_LEN)
        ids, mask = definition([TEXTS[name]], MAX_LEN)
        assert row == ids[0, : int(mask.sum())].tolist() and all(type(i) is int for i in row)
    assert tok.encode(TEXTS[name], 1) == [1] and tok.encode(TEXTS[name], 0) == []


@pytest.mark.parametrize("name", sorted(TEXTS))
def test_tokenize_count_tokens_and_hash_keep_their_meaning(name):
    tok = HashingTokenizer(vocab_size=5000)
    words = WORDS.findall(TEXTS[name].lower())
    assert tok.tokenize(TEXTS[name]) == words and tok.count_tokens(TEXTS[name]) == len(words)
    assert [tok._hash(w) for w in words] == [word_id(w, 5000) for w in words]
    assert (tok.words, tok.word_hits, len(tok._ids)) == (0, 0, 0)  # none of them reads the map


def test_texts_may_be_any_sequence_and_the_arrays_are_the_callers():
    tok = HashingTokenizer()
    texts = tuple(batch_of(5, seed=9))
    ids, mask = tok.encode_batch(texts, 64)
    ids[:] = -1  # the caller may write into what it was given
    mask[:] = 7
    same(tok.encode_batch(list(texts), 64), definition(texts, 64))


def test_threads_sharing_one_tokenizer_read_the_definition(monkeypatch):
    """The map has no lock: threads that miss, store and start it afresh under
    each other must each still read the definition's arrays."""
    monkeypatch.setattr(_tokenizer, "_MAP_ENTRIES", 128)  # so that they also clear under each other
    tok = HashingTokenizer()
    batches = [batch_of(33, seed=s) for s in range(12)]
    wanted = [definition(b, 64) for b in batches]
    failures = []

    def work(k):
        try:
            for _ in range(5):
                same(tok.encode_batch(batches[k], 64), wanted[k])
        except BaseException as e:  # noqa: BLE001 - handed to the main thread, which raises it
            failures.append(e)

    threads = [threading.Thread(target=work, args=(k,), daemon=True) for k in range(12)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    if failures:
        raise failures[0]
    assert 0 < tok.words and 0 <= tok.word_hits <= tok.words


def test_the_other_tokenizers_keep_no_word_counts(tmp_path):
    vocab = tmp_path / "vocab.txt"
    vocab.write_text("\n".join(["[PAD]", "[UNK]", "[CLS]", "[SEP]", "hello", "world"]) + "\n")
    tok = _tokenizer.WordPieceTokenizer(str(vocab))
    tok.encode_batch(["hello world"], 16)
    assert not hasattr(tok, "words") and not hasattr(tok, "word_hits")
    assert not hasattr(_tokenizer.HFTokenizerAdapter, "words")
