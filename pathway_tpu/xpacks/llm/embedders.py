"""Embedders (reference: xpacks/llm/embedders.py — BaseEmbedder:64,
OpenAIEmbedder:85, LiteLLMEmbedder:180, SentenceTransformerEmbedder:270,
GeminiEmbedder:330).

The local embedder is TPU-native: a flax encoder jitted per padded shape
(`pathway_tpu/xpacks/llm/_encoder.py`), fed whole ticks at once through the
engine's batched-UDF path and forwarding each in length-sorted groups where
the lengths differ — this is the BASELINE.md "embed docs/sec/chip"
configuration. API embedders (OpenAI/LiteLLM/Gemini) keep the reference
surface and degrade with a clear error when the client lib / network is
unavailable."""

from __future__ import annotations

import asyncio
from typing import Any, Sequence

import numpy as np

from pathway_tpu.internals import expression as expr_mod
from pathway_tpu.internals.udfs import UDF
from pathway_tpu.xpacks.llm._tokenizer import _bucket_len

# Texts a length-sorted group of one embed batch holds; a multiple of the
# batch ladder's floor. Fixed from chip runs at 16, 32 and 64 (PERF.md
# section 6, PR 31): the code decides from the lengths it is handed whether
# to group at all, so nobody has to tune this.
_GROUP = 32

# Padded positions a group holds at most: a full group on the 512 rung, the
# largest group any forward held before documents longer than a chunk came.
# Above that rung a group closes by positions before it closes by rows.
_GROUP_POSITIONS = _GROUP * 512

# what a batch's forwards report that adds up over them; the rest (bucket
# sizes, an expert's largest load) is the largest forwarded
_ADDED = (
    "tokens_padded", "expert_rows_useful", "expert_rows_computed",
    "attn_pairs_allowed", "attn_pairs_visited", "ssm_chunks_useful", "ssm_chunks_visited",
    "gdn_chunks_useful", "gdn_chunks_visited",
)


def _group_rows(rung: int) -> int:
    """Rows a group on ``rung`` holds at most: ``_GROUP``, or the power of
    two that fits ``_GROUP_POSITIONS`` (16 on the 1,024 rung, 1 on the
    16,384 one)."""
    return min(_GROUP, 1 << (max(1, _GROUP_POSITIONS // rung).bit_length() - 1))


def length_groups(lengths, width: int, max_len: int, batch_bucket):
    """The plan of one embed batch: which rows ride together, at which rung.

    ``lengths`` are the texts' real token counts, ``width`` the rung the whole
    batch was padded to (its longest text's), ``batch_bucket`` the runtime's
    padding of a count at a width. The rows, sorted by length, are cut into
    groups from the long end, each on the rung of its own longest member and
    closed at ``_GROUP`` rows or at ``_GROUP_POSITIONS`` padded positions,
    whichever comes first (``_group_rows``): ``[(rows, rung), ...]``,
    shortest first. Up to the 512 rung a group closes by rows, and the
    remainder rides at the short end, where its padding rows cost least.
    Above it a group closes by positions: it holds the rows of its own rung
    (a row lifted a rung there pads by a thousand positions and more, which
    costs more than the forward it saves) and fills the padding rows its
    count is rounded up to with the next longest, which ride for nothing.
    ``None`` says forward the batch whole: it is one group anyway, or it fits
    a group's positions and the plan's padded positions are not under three
    quarters of the whole batch's, so a split would pay per forward (a
    launch, a read of the weights) for nothing."""
    n = len(lengths)
    by_rows = _group_rows(width) == _GROUP  # every group closes by rows
    if by_rows and n <= _GROUP:
        return None
    order = np.argsort(lengths, kind="stable")
    ladder = np.array([_bucket_len(1 << e, max_len) for e in range(4, max(width, 16).bit_length() + 1)])
    rungs = np.minimum(ladder[np.searchsorted(ladder[:-1], np.asarray(lengths)[order])], width).tolist()
    groups, planned, end = [], 0, n
    while end > 0:
        rung = rungs[end - 1]
        start = max(0, end - _group_rows(rung))
        if _group_rows(rung) == _GROUP:
            rows = batch_bucket(_GROUP, rung)
        else:
            while rungs[start] != rung:
                start += 1
            rows = batch_bucket(end - start, rung)
            start = max(0, end - rows)
        groups.append((order[start:end], rung))
        planned += rows * rung
        end = start
    groups.reverse()
    whole = batch_bucket(n, width) * width
    if len(groups) == 1 or ((by_rows or whole <= _GROUP_POSITIONS) and 4 * planned >= 3 * whole):
        return None
    return groups


def _forward_groups(runtime, ids, mask, plan, built: set[int], **asked) -> tuple[np.ndarray, list[tuple[np.ndarray, dict]]]:
    """Forwards a planned batch group by group, every forward dispatched
    before the first result is fetched: vectors in the rows' own order, and
    each group's (rows, what the runtime forwarded). ``asked`` goes to every
    ``dispatch`` (a trunk's ``routing=True``).

    A group closed by rows rides at batch shape ``_GROUP`` (a remainder is
    padded to it), so those shapes are ``_GROUP`` x the length ladder up to
    the 512 rung. Which rungs a batch's groups land on differs from batch to
    batch, so the first call that reaches such a rung builds every rung below
    it as well (``built``): a later batch compiles nothing. A group closed by
    positions rides at the runtime's padding of its own count, a power of two
    that may be under 8; those shapes, three a rung at most and each up to a
    whole ``_GROUP_POSITIONS`` forward to build, are built when first met."""
    by_rows = [rung for _, rung in plan if _group_rows(rung) == _GROUP]
    top = max(by_rows, default=0)
    if top and top not in built:
        ladder = {min(_bucket_len(t, runtime.max_len), top) for t in range(1, top + 1)}
        for rung in sorted(ladder - built):
            runtime.dispatch(np.zeros((_GROUP, rung), np.int32), np.ones((_GROUP, rung), np.float32))
            built.add(rung)
    pending = []
    for rows, rung in plan:
        group_ids, group_mask = ids[rows, :rung], mask[rows, :rung]
        if len(rows) < _group_rows(rung) == _GROUP:  # the remainder: padded, or its shape would be a new one
            pad = ((0, _GROUP - len(rows)), (0, 0))
            group_ids, group_mask = np.pad(group_ids, pad), np.pad(group_mask, pad)
        pending.append(runtime.dispatch(group_ids, group_mask, **asked))
    results = [fetch() for fetch in pending]
    first = results[0][0]
    out = np.empty((len(ids),) + first.shape[1:], first.dtype)
    for (rows, _), (vectors, _info) in zip(plan, results):
        out[rows] = vectors[: len(rows)]
    return out, [(rows, info) for (rows, _), (_, info) in zip(plan, results)]


def _summed(infos: list[dict]) -> dict:
    """What one call forwarded, from what each of its forwards reports:
    ``_ADDED`` add up, the rest is the largest (a trunk's name is the same
    in each)."""
    total = dict(infos[0])
    for info in infos[1:]:
        for key, value in info.items():
            total[key] = total[key] + value if key in _ADDED else max(total[key], value)
    return total


def _word_counts(tokenizer) -> tuple[int, int] | None:
    """What a tokenizer that keeps a word-to-id map has counted so far: (words
    looked up, those the map answered); ``None`` of one that keeps none."""
    words = getattr(tokenizer, "words", None)
    return None if words is None else (words, tokenizer.word_hits)


class BaseEmbedder(UDF):
    """UDF str -> np.ndarray; also callable on expressions."""

    def get_embedding_dimension(self, **kwargs) -> int:
        out = self.func("pathway", **kwargs)  # type: ignore[misc]
        if asyncio.iscoroutine(out):
            out = asyncio.run(out)
        return len(out)

    def __call__(self, input: Any, **kwargs: Any) -> expr_mod.ColumnExpression:
        return super().__call__(input, **kwargs)


class SentenceTransformerEmbedder(BaseEmbedder):
    """Local embedder on TPU
    (reference name: xpacks/llm/embedders.py:270 — there torch
    sentence-transformers; here the flax encoder; pass a model name of a
    locally-cached HF tokenizer to reuse its vocab, otherwise a hashing
    tokenizer is used).

    ``trunk=`` (a ``TrunkConfig``, or the path of a model's published
    ``config.json``) runs a decoder-style trunk from ``_trunk.py``'s layer
    table in the encoder's place: sparse experts, latent or grouped-query
    (window and full) attention, a multi-stream, plain or parallel
    residual; causal, pooled at the last real token. Same ``embed_batch``,
    tokenizer resolution, pad ladder and spans; ``dim``/``depth``/``heads``
    are then the config's.

    The pad ladder: a batch is padded to its longest text's length rung
    (16, 32, 64, ... ``max_len``) and its count to a power of two from 8
    (from 4, 2 and 1 on the 1,024, 2,048 and longer rungs: the floor never
    pads a batch past 8 x 512 positions). A batch of more than ``_GROUP``
    texts whose lengths differ enough is forwarded in length-sorted groups
    of ``_GROUP`` instead, each on its own longest member's rung
    (``length_groups``). No group holds more than ``_GROUP_POSITIONS`` =
    16,384 padded positions: above the 512 rung a group closes by positions
    (16 rows on the 1,024 rung, ... one on the 16,384 one), so a batch of
    whole documents is forwarded rung by rung however few its texts are.
    The vectors are the same and come back in the caller's order."""

    def __init__(
        self,
        model: str = "pathway-tpu/minilm-384",
        call_kwargs: dict = {},
        device: str = "tpu",
        *,
        dim: int = 384,
        depth: int = 6,
        heads: int = 6,
        max_len: int = 512,
        mesh: Any = None,
        batch_size: int = 1024,
        trunk: Any = None,
        **init_kwargs,
    ):
        import os

        from pathway_tpu.xpacks.llm._bert import _find_model_dir
        from pathway_tpu.xpacks.llm._encoder import EncoderRuntime
        from pathway_tpu.xpacks.llm._tokenizer import (
            HashingTokenizer,
            HFTokenizerAdapter,
            WordPieceTokenizer,
        )

        # resolve a pretrained checkpoint: local dir or HF cache; the
        # random-init flax trunk + hashing tokenizer remain the offline
        # fallback (reference loads sentence-transformers checkpoints,
        # embedders.py:270)
        trunk_config = None
        if trunk is not None:
            from pathway_tpu.xpacks.llm._trunk import TrunkConfig

            trunk_config = TrunkConfig.coerce(trunk)
        model_dir = _find_model_dir(model)
        model_path = None
        if model_dir is not None and os.path.exists(
            os.path.join(model_dir, "model.safetensors")
        ):
            model_path = model_dir
        # tokenizer priority: exact HF implementation when importable →
        # our WordPiece (BertTokenizer-parity, dependency-free) → hashing
        self.tokenizer: Any = None
        for candidate in ([model_dir] if model_dir else []) + [model]:
            try:
                self.tokenizer = HFTokenizerAdapter(candidate)
                break
            except Exception:
                pass
        vocab_txt = (
            os.path.join(model_dir, "vocab.txt") if model_dir else None
        )
        if (
            self.tokenizer is None
            and vocab_txt
            and os.path.exists(vocab_txt)
        ):
            lowercase = True
            tok_cfg = os.path.join(model_dir, "tokenizer_config.json")
            if os.path.exists(tok_cfg):
                import json

                with open(tok_cfg) as f:
                    lowercase = bool(
                        json.load(f).get("do_lower_case", True)
                    )
            self.tokenizer = WordPieceTokenizer(
                vocab_txt, lowercase=lowercase
            )
        if self.tokenizer is None:
            self.tokenizer = (
                HashingTokenizer()
                if trunk_config is None
                else HashingTokenizer(trunk_config.vocab_size)
            )
        vocab_size = self.tokenizer.vocab_size
        if model_path is not None and isinstance(
            self.tokenizer, HashingTokenizer
        ):
            # hash-bucket ids are unrelated to the checkpoint's vocabulary
            # — pretrained weights would emit noise; use the random trunk
            import logging

            logging.getLogger("pathway_tpu").warning(
                "checkpoint %s has weights but no usable tokenizer "
                "(vocab.txt missing); falling back to the random-init "
                "encoder",
                model,
            )
            model_path = None
        if trunk_config is not None:
            from pathway_tpu.xpacks.llm._trunk import TrunkRuntime

            if vocab_size > trunk_config.vocab_size:
                raise ValueError(
                    f"the tokenizer has {vocab_size} ids, the trunk's embedding "
                    f"{trunk_config.vocab_size} rows"
                )
            self.runtime: Any = TrunkRuntime(trunk_config, max_len=max_len, mesh=mesh)
        else:
            self.runtime = EncoderRuntime(
                vocab_size=vocab_size,
                dim=dim,
                depth=depth,
                heads=heads,
                max_len=max_len,
                mesh=mesh,
                model_path=model_path,
            )
        self.model = model
        self.kwargs = call_kwargs
        # Flight Recorder: embed batch latency and documents embedded,
        # measured where the work happens
        from pathway_tpu.observability import REGISTRY

        m_batch_seconds = REGISTRY.histogram(
            "pathway_embed_batch_seconds",
            "embedder batch latency (tokenize + device forward)",
            labelnames=("model",),
        ).labels(model)
        m_docs = REGISTRY.counter(
            "pathway_embed_docs_total",
            "documents embedded",
            labelnames=("model",),
        ).labels(model)
        from pathway_tpu.observability.tracing import get_tracer
        from pathway_tpu.serving.metrics import occupancy_histogram

        m_occupancy = occupancy_histogram()
        _tracer = get_tracer()
        self._built: set[int] = set()  # the group rungs this embedder's forward is compiled for

        def embed_batch(texts: Sequence[str]) -> list[np.ndarray]:
            import time as _time

            # Trace Weaver: one child span per device batch (nested under
            # the operator span of the tick that carried these rows), split
            # into its host half and its device half. The counts ride on
            # the spans, so the useful share of the padded tokens can be
            # cut to any part of a window.
            docs = len(texts)
            with _tracer.span("embed.batch", model=model, docs=docs) as sp:
                t0 = _time.perf_counter()
                with _tracer.span("embed.tokenize", docs=docs) as tok:
                    looked_up = _word_counts(self.tokenizer)
                    ids, mask = self.tokenizer.encode_batch(
                        # runtime.max_len is clamped to the checkpoint's
                        # position table; exceeding it would silently clamp
                        # position ids
                        [str(t) for t in texts], self.runtime.max_len
                    )
                    lengths = mask.sum(axis=1).astype(np.int64)
                    tokens_real = int(lengths.sum())
                    len_bucket = int(ids.shape[1])
                    tok.set_attribute("tokens_real", tokens_real)
                    tok.set_attribute("len_bucket", len_bucket)
                    if looked_up is not None:  # this call's share of the tokenizer's counts
                        words, hits = _word_counts(self.tokenizer)
                        tok.set_attribute("words", words - looked_up[0])
                        tok.set_attribute("word_hits", hits - looked_up[1])
                with _tracer.span("embed.forward") as fwd:
                    out, parts = self._forward_planned(ids, mask, lengths)
                    # what the runtime really forwarded (the padded shapes,
                    # a trunk's expert rows), as the runtime itself counts it
                    fwd.set_attribute("tokens_real", tokens_real)
                    fwd.set_attribute("groups", len(parts))
                    for key, value in _summed([info for _, info in parts]).items():
                        fwd.set_attribute(key, value)
                dt = _time.perf_counter() - t0
            m_batch_seconds.observe(dt, exemplar=sp.trace_id)
            m_docs.inc(docs)
            # Surge Gate ladder visibility: how well realized batches
            # fill the encoder's pad bucket (the shape XLA compiled for),
            # one observation per batch forwarded
            for rows, info in parts:
                m_occupancy.labels("embed", str(info["batch_bucket"])).observe(
                    min(1.0, len(rows) / info["batch_bucket"])
                )
            return [out[i] for i in range(docs)]

        self._embed_batch = embed_batch
        super().__init__(
            return_type=np.ndarray, max_batch_size=batch_size, deterministic=True
        )
        self._prepare(self._single)
        self._batched = True
        # batched path: fn receives a list of texts
        self._fn = embed_batch

    def _forward_planned(self, ids, mask, lengths, **asked):
        """One tokenized batch as ``embed_batch`` forwards it: whole, or group
        by group as ``length_groups`` plans it. Vectors in the rows' order and
        each forward's (rows, what the runtime forwarded); ``asked`` goes to
        the runtime with every forward."""
        plan = length_groups(lengths, int(ids.shape[1]), self.runtime.max_len, self.runtime.batch_bucket)
        if plan is None:
            out, whole = self.runtime.forward(ids, mask, **asked)
            return out, [(np.arange(len(ids)), whole)]
        return _forward_groups(self.runtime, ids, mask, plan, self._built, **asked)

    def _single(self, text: str) -> np.ndarray:
        return self._embed_batch([text])[0]

    @property
    def func(self):
        return self._single

    def get_embedding_dimension(self, **kwargs) -> int:
        return self.runtime.dim


class _ApiEmbedder(BaseEmbedder):
    """Shared plumbing for API-backed embedders."""

    def __init__(self, capacity=None, retry_strategy=None, cache_strategy=None, **kwargs):
        self._api_kwargs = kwargs
        super().__init__(
            return_type=np.ndarray,
            cache_strategy=cache_strategy,
            retry_strategy=retry_strategy,
        )
        self._prepare(self._embed)

    async def _embed(self, input: str, **kwargs) -> np.ndarray:
        raise NotImplementedError


class OpenAIEmbedder(_ApiEmbedder):
    """(reference: embedders.py:85) — requires the `openai` package +
    network access."""

    def __init__(self, model: str = "text-embedding-3-small", **kwargs):
        self.model = model
        super().__init__(**kwargs)

    async def _embed(self, input: str, **kwargs) -> np.ndarray:
        try:
            import openai  # type: ignore[import-not-found]
        except ImportError as exc:
            raise ImportError(
                "OpenAIEmbedder requires the `openai` package; use "
                "SentenceTransformerEmbedder for on-TPU embedding"
            ) from exc
        client = openai.AsyncOpenAI(**self._api_kwargs)
        ret = await client.embeddings.create(
            input=[input or "."], model=kwargs.get("model", self.model)
        )
        return np.array(ret.data[0].embedding)


class LiteLLMEmbedder(_ApiEmbedder):
    """(reference: embedders.py:180)"""

    def __init__(self, model: str = "", **kwargs):
        self.model = model
        super().__init__(**kwargs)

    async def _embed(self, input: str, **kwargs) -> np.ndarray:
        try:
            import litellm  # type: ignore[import-not-found]
        except ImportError as exc:
            raise ImportError("LiteLLMEmbedder requires `litellm`") from exc
        ret = await litellm.aembedding(
            input=[input or "."], model=kwargs.get("model", self.model)
        )
        return np.array(ret.data[0]["embedding"])


class GeminiEmbedder(_ApiEmbedder):
    """(reference: embedders.py:330)"""

    def __init__(self, model: str = "models/embedding-001", **kwargs):
        self.model = model
        super().__init__(**kwargs)

    async def _embed(self, input: str, **kwargs) -> np.ndarray:
        try:
            import google.generativeai as genai  # type: ignore[import-not-found]
        except ImportError as exc:
            raise ImportError("GeminiEmbedder requires `google-generativeai`") from exc
        ret = genai.embed_content(
            model=kwargs.get("model", self.model), content=input or "."
        )
        return np.array(ret["embedding"])


class OpenAIEmbedderWithDimensions(OpenAIEmbedder):
    pass
