"""Native parser depth tests (reference strategies:
python/pathway/xpacks/llm/parsers.py:82-775 — chunking modes, table
extraction, paged parsing, per-page vision parsing)."""

from __future__ import annotations

import pytest

from pathway_tpu.xpacks.llm.parsers import (
    DoclingParser,
    Element,
    ImageParser,
    SlideParser,
    UnstructuredParser,
    chunk_by_title,
    chunk_elements_basic,
    native_partition,
)

FIXTURE = b"""INTRODUCTION

This is the opening paragraph of the document. It describes the subject
at some length.

- first bullet
- second bullet

METHODS

| name | value |
|------|-------|
| a    | 1     |
| b    | 2     |

The methods paragraph explains how the values were obtained.
\x0cRESULTS

The results paragraph appears on the second page of the document.
"""


def test_native_partition_classifies_elements():
    els = native_partition(FIXTURE)
    cats = [e.category for e in els]
    assert cats == [
        "Title",
        "NarrativeText",
        "ListItem",
        "Title",
        "Table",
        "NarrativeText",
        "Title",
        "NarrativeText",
    ]
    # table extraction produced html
    table = next(e for e in els if e.category == "Table")
    assert "<table>" in table.metadata["text_as_html"]
    assert "<td>a</td>" in table.metadata["text_as_html"]
    # form feed advanced the page
    assert els[-1].metadata["page_number"] == 2
    assert els[0].metadata["page_number"] == 1


def test_single_mode_joins_everything():
    docs = UnstructuredParser(chunking_mode="single").parse(FIXTURE)
    assert len(docs) == 1
    text, meta = docs[0]
    assert "INTRODUCTION" in text and "RESULTS" in text


def test_elements_mode_one_chunk_per_element():
    docs = UnstructuredParser(chunking_mode="elements").parse(FIXTURE)
    assert len(docs) == 8
    assert docs[0][1]["category"] == "Title"


def test_paged_mode_groups_by_page():
    docs = UnstructuredParser(chunking_mode="paged").parse(FIXTURE)
    assert len(docs) == 2
    assert "INTRODUCTION" in docs[0][0] and "RESULTS" not in docs[0][0]
    assert "RESULTS" in docs[1][0]


def test_basic_mode_respects_max_characters():
    docs = UnstructuredParser(
        chunking_mode="basic", chunking_kwargs={"max_characters": 120}
    ).parse(FIXTURE)
    assert len(docs) > 2
    assert all(len(text) <= 120 for text, _m in docs)


def test_by_title_mode_starts_sections_at_titles():
    docs = UnstructuredParser(
        chunking_mode="by_title", chunking_kwargs={"max_characters": 10_000}
    ).parse(FIXTURE)
    # three titles -> three sections
    assert len(docs) == 3
    assert docs[0][0].startswith("INTRODUCTION")
    assert docs[1][0].startswith("METHODS")
    assert docs[2][0].startswith("RESULTS")


def test_chunk_basic_splits_oversized_elements():
    els = [Element("x" * 950)]
    chunks = chunk_elements_basic(els, max_characters=400)
    assert [len(c.text) for c in chunks] == [400, 400, 150]


def test_chunk_by_title_packs_within_sections():
    els = [
        Element("Top", "Title"),
        Element("a" * 90),
        Element("b" * 90),
        Element("Next", "Title"),
        Element("c" * 90),
    ]
    chunks = chunk_by_title(els, max_characters=120)
    texts = [c.text for c in chunks]
    assert texts[0].startswith("Top")
    assert any(t.startswith("Next") for t in texts)


def test_invalid_chunking_mode_raises():
    with pytest.raises(ValueError, match="chunking_mode"):
        UnstructuredParser(chunking_mode="bogus")


def test_docling_fallback_emits_markdown_titles():
    docs = DoclingParser(chunking_mode="single").parse(FIXTURE)
    assert "# INTRODUCTION" in docs[0][0]


def test_image_parser_uses_vision_llm():
    seen = {}

    def vision(prompt: str, image: bytes) -> str:
        seen["prompt"] = prompt
        seen["n"] = len(image)
        return "a chart with three bars"

    docs = ImageParser(llm=vision).parse(b"\x89PNG fake image bytes")
    assert docs == [("a chart with three bars", {"parser": "image"})]
    assert seen["n"] > 0 and "Describe" in seen["prompt"]


def test_image_parser_without_llm_raises():
    with pytest.raises(ValueError, match="vision"):
        ImageParser().parse(b"img")


def test_slide_parser_splits_pdf_pages():
    PdfWriter = pytest.importorskip("pypdf").PdfWriter

    import io as _io

    writer = PdfWriter()
    writer.add_blank_page(width=72, height=72)
    writer.add_blank_page(width=72, height=72)
    buf = _io.BytesIO()
    writer.write(buf)

    calls = []

    def vision(prompt: str, image: bytes) -> str:
        calls.append(len(image))
        return f"slide {len(calls)}"

    docs = SlideParser(llm=vision).parse(buf.getvalue())
    assert [d[0] for d in docs] == ["slide 1", "slide 2"]
    assert [d[1]["page_number"] for d in docs] == [1, 2]
