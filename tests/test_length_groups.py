"""An embed batch forwarded in length-sorted groups (``xpacks/llm/embedders.py``
``length_groups``, ``_forward_groups``): the plan decides from the lengths,
the vectors are the whole batch's, the shapes form a closed set, the span
counts what was forwarded. On the CPU, a small encoder and the toy trunk."""

import json
import os

import numpy as np
import pytest

from pathway_tpu.observability import tracing
from pathway_tpu.xpacks.llm import embedders
from pathway_tpu.xpacks.llm._encoder import _bucket_batch as pow2_from_8
from pathway_tpu.xpacks.llm.embedders import SentenceTransformerEmbedder, length_groups

GROUP = embedders._GROUP
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# bf16 activations: a row forwarded beside other rows, at another width, may
# round otherwise (the benchmark's own limit on a served vector is 0.02)
BF16_TOL = 0.01


def lognormal_lengths(n, mean, sigma, low, high, seed=0):
    rng = np.random.default_rng(seed)
    mu = np.log(mean) - sigma**2 / 2
    return np.clip(np.rint(rng.lognormal(mu, sigma, n)), low, high).astype(int)


def texts_of(lengths, seed=0):
    """One word a token after the leading CLS: ``length`` tokens a text."""
    rng = np.random.default_rng(seed)
    words = ["w" + "".join(rng.choice(list("abcdefghij"), 4)) for _ in range(256)]
    return [" ".join(rng.choice(words, int(n) - 1)) for n in lengths]


def width_of(lengths, max_len=512):
    return embedders._bucket_len(int(max(lengths)), max_len)


PASSAGES = lognormal_lengths(256, 57, 0.4, 5, 256)  # MS MARCO's passages, with the CLS
CHUNKS = lognormal_lengths(64, 381, 0.25, 51, 501, seed=1)  # the splitter's chunks

# -- the plan -----------------------------------------------------------------


def test_the_plan_of_marco_like_passages():
    plan = length_groups(PASSAGES, width_of(PASSAGES), 512, pow2_from_8)
    assert len(plan) == 256 // GROUP
    assert sorted(np.concatenate([rows for rows, _ in plan])) == list(range(256))
    rungs = [rung for _, rung in plan]
    assert rungs == sorted(rungs) and rungs[-1] == 256 and rungs[0] < 64
    last = 0
    for rows, rung in plan:
        assert len(rows) == GROUP
        # sorted by length, each group on the rung of its own longest member
        assert last <= PASSAGES[rows].min() and PASSAGES[rows].max() <= rung
        assert rung == embedders._bucket_len(int(PASSAGES[rows].max()), 512)
        last = PASSAGES[rows].max()
    assert GROUP * sum(rungs) < 0.6 * 256 * 256


@pytest.mark.parametrize(
    "lengths, engages",
    [
        (PASSAGES[:GROUP], False),  # one group anyway
        (PASSAGES[:7], False),
        (np.array([5] * GROUP + [256]), True),  # one text more: the 32 short ones ride at 16
        (CHUNKS, False),  # uniformly long: every group on the top rung
        (np.array([100] * GROUP + [200] * GROUP), False),  # 128 + 256 of 2 x 256: three quarters, not under
        (np.array([60] * GROUP + [200] * GROUP), True),  # 64 + 256 of 2 x 256
        (np.array([200] * (2 * GROUP)), False),
        (np.array([0] * GROUP + [9] * GROUP), False),  # all on the floor rung
    ],
)
def test_the_plan_engages_only_where_it_saves(lengths, engages):
    plan = length_groups(lengths, width_of(lengths), 512, pow2_from_8)
    assert (plan is not None) == engages
    if engages:
        whole = pow2_from_8(len(lengths)) * width_of(lengths)
        assert 4 * GROUP * sum(rung for _, rung in plan) < 3 * whole


def test_a_remainder_rides_at_the_short_end():
    lengths = np.concatenate([PASSAGES[: 2 * GROUP], [3, 4, 5, 6, 2, 300]])
    plan = length_groups(lengths, 512, 512, pow2_from_8)
    assert [len(rows) for rows, _ in plan] == [6] + [GROUP] * 2
    assert sorted(lengths[plan[0][0]]) == sorted(lengths)[:6]
    assert plan[0][1] == width_of(sorted(lengths)[:6]) < 64 and plan[-1][1] == 512


def test_a_rung_is_never_wider_than_the_batch():
    # a tokenizer that pads to a width off the ladder: the slices stay inside it
    lengths = np.array([5] * GROUP + [90] * GROUP)
    plan = length_groups(lengths, 100, 512, pow2_from_8)
    assert [rung for _, rung in plan] == [16, 100]


def test_on_a_mesh_a_group_is_what_the_runtime_makes_of_it():
    # 64 devices: a group of 32 is padded to 64 rows, as the whole batch of 64 is
    lengths = np.array([5] * GROUP + [60] * GROUP)
    assert length_groups(lengths, 64, 512, lambda n, width=0: max(64, pow2_from_8(n))) is None
    assert length_groups(lengths, 64, 512, pow2_from_8) is not None


# -- the vectors --------------------------------------------------------------


@pytest.fixture(scope="module")
def embedder():
    return SentenceTransformerEmbedder(dim=32, depth=1, heads=2, max_len=256)


def forward_span():
    return [r for r in tracing.get_tracer().spans() if r.name == "embed.forward"][-1]


def whole(embedder, texts):
    ids, mask = embedder.tokenizer.encode_batch(texts, embedder.runtime.max_len)
    return embedder.runtime.forward(ids, mask)


@pytest.mark.parametrize(
    "lengths",
    [PASSAGES, np.concatenate([PASSAGES[:70], [256]]), PASSAGES[::-1][:96]],
    ids=["256-passages", "71-with-a-remainder", "96-reversed"],
)
def test_grouped_vectors_are_the_whole_batchs_in_the_callers_order(embedder, lengths):
    texts = texts_of(lengths)
    got = np.stack(embedder._embed_batch(texts))
    span = forward_span().attributes
    assert span["groups"] == -(-len(texts) // GROUP) > 1
    want, info = whole(embedder, texts)
    assert np.linalg.norm(got - want, axis=1).max() < BF16_TOL
    assert span["tokens_padded"] < 0.75 * info["tokens_padded"]
    assert span["tokens_real"] == int(sum(lengths))


@pytest.mark.parametrize(
    "lengths",
    [PASSAGES[:GROUP], PASSAGES[:3], np.full(2 * GROUP, 120)],
    ids=["one-group", "three-texts", "64-alike"],
)
def test_a_batch_that_does_not_engage_is_forwarded_whole(embedder, lengths):
    texts = texts_of(lengths, seed=2)
    got = np.stack(embedder._embed_batch(texts))
    span = forward_span().attributes
    want, info = whole(embedder, texts)
    assert np.array_equal(got, want)
    assert span == {"groups": 1, "tokens_real": int(sum(lengths)), **info}


def test_the_span_counts_what_was_forwarded(embedder):
    from pathway_tpu.serving.metrics import occupancy_histogram

    texts = texts_of(PASSAGES, seed=3)

    child = occupancy_histogram().labels("embed", str(GROUP))
    before = child.count
    embedder._embed_batch(texts)
    plan = length_groups(PASSAGES, 256, 256, embedder.runtime.batch_bucket)
    assert forward_span().attributes == {
        "groups": len(plan),
        "tokens_real": int(PASSAGES.sum()),
        "tokens_padded": GROUP * sum(rung for _, rung in plan),
        "batch_bucket": GROUP,
        "len_bucket": 256,
    }
    assert child.count - before == len(plan)  # one observation a group
    names = [r.name for r in tracing.get_tracer().spans()][-3:]
    assert sorted(names) == ["embed.batch", "embed.forward", "embed.tokenize"]  # one span a call


# -- the shapes ---------------------------------------------------------------


@pytest.fixture(scope="module")
def compiles():
    """Every program JAX builds from here on, as the benchmark's window counts them."""
    import jax.monitoring

    built = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, _seconds, **_kw: built.append(event)
        if event == "/jax/core/compile/backend_compile_duration"
        else None
    )
    return built


@pytest.fixture(scope="module")
def engaged():
    """An embedder after its first engaged call, whose groups sit on the 16
    and the 256 rung only."""
    embedder = SentenceTransformerEmbedder(dim=32, depth=1, heads=2, max_len=256)
    embedder._embed_batch(texts_of([5] * GROUP + [250] * GROUP))
    return embedder


def test_the_first_engaged_call_builds_every_rung_below_its_own(engaged):
    # (GROUP, 16 / 32 / 64 / 128 / 256); the batch never went whole
    assert engaged.runtime._fwd._cache_size() == 5


@pytest.mark.parametrize(
    "lengths",
    [
        PASSAGES,
        [20] * GROUP + [40] * GROUP + [100] * GROUP,  # rungs 32, 64, 128: none met before
        [5] * 3 + [30] * GROUP + [256],  # a remainder of 4, padded to a group
        [9] * 300 + [256] * 30,
    ],
    ids=["passages", "the-middle-rungs", "a-remainder", "330-texts"],
)
def test_no_later_engaged_call_compiles(engaged, compiles, lengths):
    before = len(compiles)
    engaged._embed_batch(texts_of(lengths, seed=4))
    assert forward_span().attributes["groups"] > 1
    assert engaged.runtime._fwd._cache_size() == 5 and len(compiles) == before


def test_a_longer_batch_later_builds_the_rungs_it_adds():
    embedder = SentenceTransformerEmbedder(dim=32, depth=1, heads=2, max_len=256)
    embedder._embed_batch(texts_of([5] * GROUP + [60] * GROUP))  # 16, 32, 64
    assert embedder.runtime._fwd._cache_size() == 3
    embedder._embed_batch(texts_of([5] * GROUP + [250] * GROUP))  # 128 and 256 besides
    assert embedder.runtime._fwd._cache_size() == 5


def test_groups_on_a_mesh_are_sharded_like_a_whole_batch():
    from pathway_tpu.parallel.mesh import make_mesh
    from pathway_tpu.xpacks.llm._encoder import EncoderRuntime

    embedder = SentenceTransformerEmbedder(dim=32, depth=1, heads=2, max_len=64)
    single = embedder.runtime
    embedder.runtime = EncoderRuntime(
        vocab_size=embedder.tokenizer.vocab_size, dim=32, depth=1, heads=2, max_len=64,
        mesh=make_mesh(8, axis_names=("data",)),
    )
    texts = texts_of([5] * GROUP + [60] * (GROUP + 3), seed=5)
    got = np.stack(embedder._embed_batch(texts))
    assert forward_span().attributes["groups"] == 3
    ids, mask = embedder.tokenizer.encode_batch(texts, 64)
    assert np.linalg.norm(got - single.forward(ids, mask)[0], axis=1).max() < BF16_TOL


# -- a trunk ------------------------------------------------------------------


@pytest.fixture(scope="module")
def trunk_embedder():
    import jax.numpy as jnp

    from pathway_tpu.xpacks.llm._trunk import TrunkConfig, TrunkRuntime

    with open(os.path.join(ROOT, "benchmarks", "configs", "xing4-29b-a4b.json"), encoding="utf-8") as f:
        body = json.load(f)
    toy = body.pop("rehearse")
    body.update({k: v for k, v in toy.items() if not isinstance(v, dict)})
    embedder = SentenceTransformerEmbedder(trunk=TrunkConfig.from_dict(body, name="toy"), max_len=32)
    # float32, so that no near-tie of two experts' scores falls the other way at another width
    embedder.runtime = TrunkRuntime(embedder.runtime.config, max_len=32, seed=5, dtype=jnp.float32)
    return embedder


@pytest.mark.parametrize(
    "lengths, groups",
    [([4] * 20 + [12] * 20 + [25] * 30, 3), ([25] * 40, 1), ([4, 9, 30], 1)],
    ids=["70-mixed", "40-alike", "three-texts"],
)
def test_a_trunk_rides_the_same_plan_and_its_expert_rows_add_up(trunk_embedder, lengths, groups):
    texts = texts_of(lengths, seed=6)
    got = np.stack(trunk_embedder._embed_batch(texts))
    span = forward_span().attributes
    want, info = whole(trunk_embedder, texts)
    assert span["groups"] == groups and span["trunk"] == "toy"
    assert np.abs(got - want).max() < 2e-4
    # a real token goes to its experts once, however the batch was cut
    assert span["expert_rows_useful"] == info["expert_rows_useful"] > 0
    assert span["expert_rows_computed"] >= span["expert_rows_useful"]
    if groups > 1:
        # the 6 shortest padded to a group, then 32 more on the floor rung, then the long ones
        assert span["tokens_padded"] == GROUP * (16 + 16 + 32) < info["tokens_padded"]
        assert span["batch_bucket"] == GROUP and span["len_bucket"] == 32
    else:
        assert {k: span[k] for k in info} == info


# -- whole documents: groups closed by positions ------------------------------------

POSITIONS = embedders._GROUP_POSITIONS
DOC_TICKS = [  # ticks of `doc-ingest-ticks`, in tokens
    [2449, 4929, 6590, 7021], [1843, 4230, 5197, 8734], [1739, 2416, 4598, 5578], [4161, 5262, 6094, 6370],
    [3324, 8344, 9653, 11879], [2214, 3429, 4214, 4240], [1210, 2100, 6132, 8622],
]


def test_the_budget_is_the_largest_group_forwarded_before():
    assert POSITIONS == GROUP * 512 == 16384
    assert [embedders._group_rows(r) for r in (16, 256, 512, 1024, 2048, 4096, 8192, 16384, 32768)] == [
        32, 32, 32, 16, 8, 4, 2, 1, 1,
    ]
    # the count's floor: 8 rows up to the 512 rung, never past 8 x 512 positions above it
    assert [pow2_from_8(1, w) for w in (0, 16, 512, 1024, 2048, 4096, 16384)] == [8, 8, 8, 4, 2, 1, 1]
    assert [pow2_from_8(n, 2048) for n in (1, 2, 3, 5, 8)] == [2, 2, 4, 8, 8]
    assert [pow2_from_8(n) for n in (1, 8, 9, 33)] == [8, 8, 16, 64]


@pytest.mark.parametrize("lengths", DOC_TICKS, ids=lambda t: "-".join(map(str, t)))
def test_a_tick_of_documents_rides_rung_by_rung_inside_the_budget(lengths):
    lengths = np.array(lengths)
    width = width_of(lengths, 16384)
    plan = length_groups(lengths, width, 16384, pow2_from_8)
    assert plan is not None and sorted(np.concatenate([rows for rows, _ in plan])) == [0, 1, 2, 3]
    rungs = [rung for _, rung in plan]
    assert rungs == sorted(rungs) and rungs[-1] == width
    for rows, rung in plan:
        # on its longest member's rung, the padded forward inside the budget, at a count that may be under 8
        own = [t for t in lengths[rows] if embedders._bucket_len(int(t), 16384) == rung]
        assert own and max(lengths[rows]) == max(own) and len(rows) <= pow2_from_8(len(own), rung)
        assert pow2_from_8(len(rows), rung) * rung <= POSITIONS and len(rows) <= embedders._group_rows(rung)
    assert sum(pow2_from_8(len(rows), rung) * rung for rows, rung in plan) <= pow2_from_8(4, width) * width


def test_the_plan_of_the_first_doc_tick_by_hand():
    plan = length_groups(np.array(DOC_TICKS[0]), 8192, 16384, pow2_from_8)
    assert [(sorted(rows.tolist()), rung) for rows, rung in plan] == [([0], 4096), ([1], 8192), ([2, 3], 8192)]
    four = length_groups(np.array([9000, 9001, 9002, 9003]), 16384, 16384, pow2_from_8)
    assert [(len(rows), rung) for rows, rung in four] == [(1, 16384)] * 4


@pytest.mark.parametrize(
    "lengths, width",
    [([11879], 16384), ([1500], 2048), ([5000, 6000], 8192), ([3000, 3100, 3200, 1500], 4096), ([600] * 16, 1024),
     ([600, 1500, 40, 1100, 2000, 700], 2048)],
    ids=["a-probe-of-12k", "a-probe-of-1500", "two-on-8192", "four-on-4096", "sixteen-on-1024", "six-saving-a-quarter"],
)
def test_one_group_inside_the_budget_goes_whole(lengths, width):
    assert length_groups(np.array(lengths), width, 16384, pow2_from_8) is None
    assert pow2_from_8(len(lengths), width) * width <= POSITIONS


def test_short_and_long_texts_in_one_batch():
    lengths = np.array([40] * 40 + [3000, 700, 9000])
    plan = length_groups(lengths, 16384, 16384, pow2_from_8)
    # the 700 alone on the 1,024 rung rides at 4 rows: three of the short ones fill them for nothing
    assert [(len(rows), rung) for rows, rung in plan] == [(5, 64), (32, 64), (4, 1024), (1, 4096), (1, 16384)]
    assert sorted(np.concatenate([rows for rows, _ in plan])) == list(range(43))


@pytest.mark.parametrize(
    "lengths, max_len",
    [(PASSAGES, 512), (CHUNKS, 512), (CHUNKS[:32], 512), (lognormal_lengths(24, 7, 0.4, 2, 31, seed=2), 512)],
    ids=["bge-ingest", "xing4-64-chunks", "xing4-chunk-ingest", "minilm-retrieve"],
)
def test_the_cells_at_chunk_widths_keep_their_plans(lengths, max_len):
    """Up to the 512 rung the plan is what it was before the budget: groups
    of ``_GROUP`` from the long end, the remainder at the short end."""
    width = width_of(lengths, max_len)
    plan = length_groups(lengths, width, max_len, pow2_from_8)
    n = len(lengths)
    order = np.argsort(lengths, kind="stable")
    old = [
        (order[start:end], min(embedders._bucket_len(int(lengths[order[end - 1]]), max_len), width))
        for start, end in zip([0] + list(range(n % GROUP or GROUP, n, GROUP)), range(n % GROUP or GROUP, n + 1, GROUP))
    ]
    if n <= GROUP or 4 * GROUP * sum(r for _, r in old) >= 3 * pow2_from_8(n) * width:
        assert plan is None
    else:
        assert [(rows.tolist(), rung) for rows, rung in plan] == [(rows.tolist(), rung) for rows, rung in old]


@pytest.fixture(scope="module")
def doc_embedder():
    """The toy grouped-query trunk at 2,048 positions, float32."""
    import jax.numpy as jnp

    from pathway_tpu.xpacks.llm._trunk import TrunkConfig, TrunkRuntime

    with open(os.path.join(ROOT, "benchmarks", "configs", "command-a-plus-05-2026.json"), encoding="utf-8") as f:
        body = json.load(f)
    toy = body.pop("rehearse")
    body.update({k: v for k, v in toy.items() if k not in ("embedder", "index", "published")})
    body["published"] = {"num_experts": 8}
    embedder = SentenceTransformerEmbedder(trunk=TrunkConfig.from_dict(body, name="toy-docs"), max_len=2048)
    embedder.runtime = TrunkRuntime(embedder.runtime.config, max_len=2048, seed=5, dtype=jnp.float32)
    return embedder


def test_documents_ride_in_groups_under_8_rows_and_alone_give_the_same_vectors(doc_embedder, compiles):
    lengths = [600, 1500, 40, 1100, 2000, 700, 300, 90, 50, 1900]
    texts = texts_of(lengths, seed=7)
    got = np.stack(doc_embedder._embed_batch(texts))
    span = forward_span().attributes
    # from the long end: four on the 2,048 rung; 700 and 600 on 1,024 and, in their two padding rows, 300 and 90; 50 and 40 on 64, a remainder padded to a group
    assert span["groups"] == 3 and span["tokens_padded"] == 4 * 2048 + 4 * 1024 + GROUP * 64
    assert span["tokens_real"] == sum(lengths) and span["batch_bucket"] == GROUP and span["len_bucket"] == 2048
    assert span["attn_pairs_visited"] > span["attn_pairs_allowed"] > 0
    shapes = doc_embedder.runtime._fwd._cache_size()
    assert shapes == 2 + 3  # (4, 2048), (4, 1024) and the ladder 16, 32, 64 of the group closed by rows: none above it
    for i in (1, 2, 4):
        alone = doc_embedder._embed_batch([texts[i]])[0]
        assert np.abs(alone - got[i]).max() < 2e-4
    alone_span = forward_span().attributes
    assert alone_span["groups"] == 1 and alone_span["batch_bucket"] == 2 and alone_span["tokens_padded"] == 2 * 2048
    # a later tick of the same rungs compiles nothing
    before = len(compiles)
    doc_embedder._embed_batch(texts_of([650, 1400, 30, 1200, 1900, 800, 280, 70, 60, 2000], seed=8))
    assert len(compiles) == before and forward_span().attributes["groups"] == 3
