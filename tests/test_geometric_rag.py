"""answer_with_geometric_rag_strategy_from_index (reference: xpacks/llm/question_answering.py:162-215) — fake-LLM test of
the doc-count doubling loop."""

import pathway_tpu as pw
from pathway_tpu.debug import table_to_dicts
from pathway_tpu.xpacks.llm.question_answering import (
    answer_with_geometric_rag_strategy_from_index,
)


class _FakeChat:
    """Answers only once enough documents are in the prompt; records the
    document counts of every call so the geometric growth is checkable."""

    def __init__(self, needed_doc: str):
        self.needed_doc = needed_doc
        self.calls: list[str] = []

    def func(self, prompt: str) -> str:
        self.calls.append(prompt)
        if self.needed_doc in prompt:
            return "the answer is 42"
        return "No information found."


def _doc_index():
    class D(pw.Schema):
        doc: str

    docs = pw.debug.table_from_rows(
        D, [(f"document number {i} about topic {i}",) for i in range(8)]
    )

    @pw.udf
    def fake_embed(text: str):
        import numpy as np

        # deterministic embedding: doc i points along axis i; other
        # text hashes to an axis
        v = np.zeros(8, dtype=np.float32)
        words = text.split()
        if len(words) > 2 and words[2].isdigit():
            v[int(words[2]) % 8] = 1.0
        else:
            v[hash(text) % 8] = 1.0
        return v

    from pathway_tpu.stdlib.indexing.vector_document_index import (
        default_brute_force_knn_document_index,
    )

    return docs, default_brute_force_knn_document_index(
        docs.doc, docs, embedder=fake_embed, dimensions=8
    )


def test_geometric_rag_from_index_doubles_docs():
    docs, index = _doc_index()

    class Q(pw.Schema):
        question: str

    queries = pw.debug.table_from_rows(Q, [("about topic 3",)])
    # the fake embedder maps this question to... whatever; the needed doc
    # is ranked somewhere in the top-4, so 1-doc and 2-doc prompts fail
    # and the loop must double up to 4
    chat = _FakeChat("document number 2")
    answers = answer_with_geometric_rag_strategy_from_index(
        queries.question,
        index,
        "doc",
        chat,
        n_starting_documents=1,
        factor=2,
        max_iterations=4,
    )
    _keys, cols = table_to_dicts(answers.table.select(a=answers))
    vals = list(cols["a"].values())
    assert vals == ["the answer is 42"], (vals, chat.calls)
    # doubling loop: successive calls carry geometrically more documents
    counts = [c.count("document number") for c in chat.calls]
    assert counts[0] == 1
    assert all(b >= a for a, b in zip(counts, counts[1:])), counts
    assert len(counts) >= 2, counts


def test_geometric_rag_from_index_no_answer_is_none():
    docs, index = _doc_index()

    class Q(pw.Schema):
        question: str

    queries = pw.debug.table_from_rows(Q, [("anything",)])
    chat = _FakeChat("THIS DOC DOES NOT EXIST")
    answers = answer_with_geometric_rag_strategy_from_index(
        queries.question,
        index,
        "doc",
        chat,
        n_starting_documents=1,
        factor=2,
        max_iterations=3,
    )
    _keys, cols = table_to_dicts(answers.table.select(a=answers))
    assert list(cols["a"].values()) == [None]
    counts = [c.count("document number") for c in chat.calls]
    assert len(counts) == 3, counts  # all max_iterations exhausted
    assert counts == sorted(counts), counts
