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
    assert length_groups(lengths, 64, 512, lambda n: max(64, pow2_from_8(n))) is None
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
