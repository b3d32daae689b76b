"""Deterministic hashing tokenizer for the local TPU encoder.

No vocabulary files / no network: tokens are hashed into a fixed id space
(feature-hashing). If a HuggingFace tokenizer is locally cached, it can be
plugged in instead (`HFTokenizerAdapter`)."""

from __future__ import annotations

import hashlib
import re
import struct
from itertools import chain, repeat
from typing import Sequence

import numpy as np

_TOKEN_RE = re.compile(r"[a-zA-Z]+|\d+|[^\sa-zA-Z\d]", re.UNICODE)

PAD_ID = 0
CLS_ID = 1
_RESERVED = 2


# The bound of a tokenizer's word-to-id map: entries, and the longest word
# worth one. A run of letters or digits longer than that is a key or a number
# and does not come again. An entry is at most some 160 bytes (the word, its
# id, its slot), so a full map is 40 MB at the very worst, however long the
# process streams.
_MAP_ENTRIES = 262_144
_MAP_WORD_CHARS = 32


class HashingTokenizer:
    """Feature hashing: a token's id is ``_hash`` of its text, nothing else.

    A word is hashed once, not once a token: each instance keeps a map from
    word to id (ids depend on ``vocab_size``, so instances share nothing),
    bounded by ``_MAP_ENTRIES``. A word longer than ``_MAP_WORD_CHARS`` is
    hashed every time and never stored. A new word that finds the map full
    makes it **start afresh**: a vocabulary that drifts keeps its current
    words, and the common words of running text are back after one hash
    each. A stream of words that never repeat pays a read that fails and a
    store above the hash, a token.

    ``words`` and ``word_hits`` count the lookups and those the map answered.
    They only grow; a caller that wants one call's share reads them before
    and after. Under concurrent callers they may lose an update, and the map
    may pass its bound by what the other callers' batches store: they feed a
    span's attributes and a memory bound, never an id.
    """

    def __init__(self, vocab_size: int = 30522, lowercase: bool = True):
        self.vocab_size = vocab_size
        self.lowercase = lowercase
        self._ids: dict[str, int] = {}
        self.words = 0
        self.word_hits = 0

    def _hash(self, token: str) -> int:
        h = struct.unpack(
            "<Q", hashlib.blake2b(token.encode(), digest_size=8).digest()
        )[0]
        return _RESERVED + (h % (self.vocab_size - _RESERVED))

    def tokenize(self, text: str) -> list[str]:
        if self.lowercase:
            text = text.lower()
        return _TOKEN_RE.findall(text)

    def _word_ids(self, words: list[str]) -> np.ndarray:
        """The ids of ``words``: one read of the map each, and the words it
        lacks hashed and stored. No lock: dictionary reads and writes are
        atomic under the interpreter lock, and two threads that miss the
        same word both hash it and store the same id."""
        ids = self._ids
        out = np.fromiter(map(ids.get, words, repeat(PAD_ID)), np.int32, len(words))
        missed = np.flatnonzero(out == PAD_ID).tolist()  # no word's id is PAD_ID
        if missed:
            hashed = []
            for i in missed:
                word = words[i]
                word_id = ids.get(word)  # missed twice in one call: hashed once
                if word_id is None:
                    word_id = self._hash(word)
                    if len(word) <= _MAP_WORD_CHARS and _MAP_ENTRIES:
                        if len(ids) >= _MAP_ENTRIES:
                            ids.clear()
                        ids[word] = word_id
                hashed.append(word_id)
            out[missed] = hashed
        self.words += len(words)
        self.word_hits += len(words) - len(missed)
        return out

    def encode(self, text: str, max_len: int) -> list[int]:
        """``[CLS] + [_hash(t) for t in tokenize(text)]``, cut to ``max_len``:
        the plain statement of what a row of ``encode_batch`` holds."""
        words = self.tokenize(text)[: max(max_len - 1, 0)]
        return ([CLS_ID] + self._word_ids(words).tolist())[:max_len]

    def encode_batch(
        self, texts: Sequence[str], max_len: int
    ) -> tuple[np.ndarray, np.ndarray]:
        """Returns (ids [B, L], mask [B, L]) padded to the smallest rung of
        the length ladder (``_bucket_len``) ≥ the longest sequence. That is
        the widest shape a forward of these texts needs, not the shape every
        text is forwarded at: the embedder reads the real lengths off the
        mask and may forward column slices ``[:, :rung]`` of length-sorted
        groups (``embedders.length_groups``).

        One pass: every word of the batch goes through the map in one
        iterator, and ids and mask are written with array operations."""
        keep = max(max_len - 1, 0)
        words = [self.tokenize(t)[:keep] for t in texts]
        counts = np.fromiter(map(len, words), np.intp, len(words))
        bucket = _bucket_len(int(counts.max(initial=0)) + 1, max_len)  # CLS and the words
        real = np.arange(bucket) <= counts[:, None]
        ids = np.full((len(words), bucket), PAD_ID, dtype=np.int32)
        ids[:, :1] = CLS_ID
        # row by row, left to right: the order the words were chained in
        ids[:, 1:][real[:, 1:]] = self._word_ids(list(chain.from_iterable(words)))
        return ids, real.astype(np.float32)

    def count_tokens(self, text: str) -> int:
        return len(self.tokenize(text))


def _bucket_len(n: int, max_len: int) -> int:
    # pad to {16, 32, 64, 128, ...} so jit compiles O(log max_len) variants
    b = 16
    while b < n:
        b *= 2
    return min(b, max_len)


class WordPieceTokenizer:
    """Real WordPiece over a local vocab.txt — the tokenization BERT/MiniLM
    checkpoints were trained with (reference embedders tokenize via the HF
    tokenizer inside sentence-transformers; this is the dependency-free
    equivalent, verified token-for-token against BertTokenizer in
    tests/test_bert_parity.py). Basic-tokenizer steps: clean, lowercase +
    strip accents (uncased models), CJK isolation, punctuation split; then
    greedy longest-match-first wordpiece with '##' continuations."""

    def __init__(
        self,
        vocab_file: str,
        lowercase: bool = True,
        max_word_chars: int = 100,
    ):
        import unicodedata

        self._ud = unicodedata
        self.vocab: dict[str, int] = {}
        with open(vocab_file, encoding="utf-8") as f:
            for i, line in enumerate(f):
                self.vocab[line.rstrip("\n")] = i
        self.vocab_size = len(self.vocab)
        self.lowercase = lowercase
        self.max_word_chars = max_word_chars
        missing = [
            tok for tok in ("[UNK]", "[CLS]", "[SEP]") if tok not in self.vocab
        ]
        if missing:
            # guessing ids here would silently produce garbage token
            # streams (ADVICE r2) — a BERT vocab without these is broken
            raise ValueError(
                f"vocab file {vocab_file!r} is missing required special "
                f"tokens {missing}"
            )
        self.pad_id = self.vocab.get("[PAD]", 0)
        self.unk_id = self.vocab["[UNK]"]
        self.cls_id = self.vocab["[CLS]"]
        self.sep_id = self.vocab["[SEP]"]
        # BertTokenizer's never_split set: literal special tokens in the
        # text pass through un-lowercased and un-split
        self.special_tokens = {
            "[PAD]", "[UNK]", "[CLS]", "[SEP]", "[MASK]"
        }

    # --- basic tokenization (mirrors BERT's BasicTokenizer) ---------------

    def _is_punct(self, ch: str) -> bool:
        cp = ord(ch)
        if (
            33 <= cp <= 47
            or 58 <= cp <= 64
            or 91 <= cp <= 96
            or 123 <= cp <= 126
        ):
            return True
        return self._ud.category(ch).startswith("P")

    def _is_cjk(self, ch: str) -> bool:
        cp = ord(ch)
        return (
            0x4E00 <= cp <= 0x9FFF
            or 0x3400 <= cp <= 0x4DBF
            or 0x20000 <= cp <= 0x2A6DF
            or 0x2A700 <= cp <= 0x2B73F
            or 0x2B740 <= cp <= 0x2B81F
            or 0x2B820 <= cp <= 0x2CEAF
            or 0xF900 <= cp <= 0xFAFF
            or 0x2F800 <= cp <= 0x2FA1F
        )

    def _basic_tokens(self, text: str) -> list[str]:
        # stage 1 — clean + CJK isolation (BertTokenizer._clean_text +
        # _tokenize_chinese_chars): \t\n\r are whitespace (NOT controls,
        # despite their Cc category); all other C* are stripped; Zs is the
        # only other whitespace class
        chars: list[str] = []
        for ch in text:
            cp = ord(ch)
            if ch in " \t\n\r":
                chars.append(" ")
                continue
            if cp == 0 or cp == 0xFFFD or self._ud.category(ch).startswith(
                "C"
            ):
                continue
            if self._ud.category(ch) == "Zs":
                chars.append(" ")
            elif self._is_cjk(ch):
                chars.extend((" ", ch, " "))
            else:
                chars.append(ch)
        # stage 2 — whitespace split, then per token: never_split check,
        # lowercase + accent strip, punctuation split
        out: list[str] = []
        for tok in "".join(chars).split():
            if tok in self.special_tokens:
                out.append(tok)
                continue
            if self.lowercase:
                tok = tok.lower()
                tok = "".join(
                    c
                    for c in self._ud.normalize("NFD", tok)
                    if self._ud.category(c) != "Mn"
                )
            buf: list[str] = []
            for ch in tok:
                if self._is_punct(ch):
                    if buf:
                        out.append("".join(buf))
                        buf.clear()
                    out.append(ch)
                else:
                    buf.append(ch)
            if buf:
                out.append("".join(buf))
        return out

    # --- wordpiece ---------------------------------------------------------

    def _wordpiece(self, word: str) -> list[int]:
        if len(word) > self.max_word_chars:
            return [self.unk_id]
        ids: list[int] = []
        start = 0
        while start < len(word):
            end = len(word)
            cur = None
            while start < end:
                piece = word[start:end]
                if start > 0:
                    piece = "##" + piece
                if piece in self.vocab:
                    cur = self.vocab[piece]
                    break
                end -= 1
            if cur is None:
                return [self.unk_id]
            ids.append(cur)
            start = end
        return ids

    def encode(self, text: str, max_len: int) -> list[int]:
        ids = [self.cls_id]
        for word in self._basic_tokens(text):
            ids.extend(self._wordpiece(word))
            if len(ids) >= max_len - 1:
                break
        ids = ids[: max_len - 1]
        ids.append(self.sep_id)
        return ids

    def encode_batch(
        self, texts: Sequence[str], max_len: int
    ) -> tuple[np.ndarray, np.ndarray]:
        encoded = [self.encode(t, max_len) for t in texts]
        longest = max((len(e) for e in encoded), default=1)
        bucket = _bucket_len(longest, max_len)
        ids = np.full((len(texts), bucket), self.pad_id, dtype=np.int32)
        mask = np.zeros((len(texts), bucket), dtype=np.float32)
        for i, e in enumerate(encoded):
            ids[i, : len(e)] = e
            mask[i, : len(e)] = 1.0
        return ids, mask

    def count_tokens(self, text: str) -> int:
        return len(self.encode(text, 1 << 30)) - 2


class HFTokenizerAdapter:
    """Wraps a locally-cached HuggingFace tokenizer (no downloads)."""

    def __init__(self, name_or_path: str):
        from transformers import AutoTokenizer

        self.tok = AutoTokenizer.from_pretrained(
            name_or_path, local_files_only=True
        )
        self.vocab_size = self.tok.vocab_size

    def encode_batch(self, texts, max_len):
        out = self.tok(
            list(texts),
            truncation=True,
            max_length=max_len,
            padding=True,
            return_tensors="np",
        )
        ids = out["input_ids"].astype(np.int32)
        mask = out["attention_mask"].astype(np.float32)
        bucket = _bucket_len(ids.shape[1], max_len)
        if ids.shape[1] < bucket:
            pad = bucket - ids.shape[1]
            ids = np.pad(ids, ((0, 0), (0, pad)))
            mask = np.pad(mask, ((0, 0), (0, pad)))
        return ids, mask

    def count_tokens(self, text: str) -> int:
        return len(self.tok.encode(text))
