"""The one general generator: a traffic mix's data file and a seed in, ticks out.

A tick is the batch of texts the engine hands a layer in one call. The mix's
file fixes how many texts a tick holds and how many words a text has; the
set of sizes is drawn from the file's own ``shape_seed`` and is therefore the
same for every ``--seed``, which only reorders the ticks and picks the words.
A window that outlasts the file's ``ticks`` goes on to another pass: the same
shapes in the same order with words drawn afresh, so no text is sent twice.
"""

from __future__ import annotations

import numpy as np

_LETTERS = np.array(list("abcdefghijklmnopqrstuvwxyz"))


def vocabulary(size: int, seed: int) -> np.ndarray:
    """``size`` distinct lower-case pseudo-words of 2 to 9 letters."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0x766F63]))
    words: dict[str, None] = {}
    while len(words) < size:
        lengths = rng.integers(2, 10, size=size)
        letters = rng.integers(0, 26, size=int(lengths.sum()))
        start = 0
        for n in lengths:
            words.setdefault("".join(_LETTERS[letters[start : start + n]]), None)
            start += n
    return np.array(list(words)[:size], dtype=object)


def _tick_sizes(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    if spec["dist"] == "fixed":
        return np.full(n, int(spec["value"]), dtype=np.int64)
    if spec["dist"] == "rungs":  # each size its stated share of the n ticks
        sizes = np.asarray(spec["sizes"], dtype=np.int64)
        shares = np.asarray(spec.get("shares", np.ones(len(sizes))), dtype=np.float64)
        exact = shares / shares.sum() * n
        counts = np.floor(exact).astype(np.int64)
        for i in np.argsort(counts - exact, kind="stable")[: n - counts.sum()]:
            counts[i] += 1  # what rounding left over, to the largest remainders
        return np.repeat(sizes, counts)
    raise ValueError(f"unknown tick size distribution {spec['dist']!r}")


def _word_counts(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown word count distribution {spec['dist']!r}")
    sigma = float(spec["sigma"])
    if "mean" in spec:  # a source states a mean: the median that gives it
        median = float(spec["mean"]) / np.exp(0.5 * sigma * sigma)
    else:
        median = float(spec["median"])
    raw = rng.lognormal(np.log(median), sigma, n)
    return np.clip(np.rint(raw), int(spec["min"]), int(spec["max"])).astype(np.int64)


def make_ticks(traffic: dict, seed: int, pass_number: int = 0) -> list[list[str]]:
    """``traffic['ticks']`` ticks of texts, reproducible from ``seed``. A
    later ``pass_number`` has the same shapes in the same order, other words."""
    n_ticks = int(traffic["ticks"])
    shape_rng = np.random.default_rng(
        np.random.SeedSequence([int(traffic["shape_seed"]), 1])
    )
    sizes = _tick_sizes(traffic["tick_size"], n_ticks, shape_rng)
    counts = [_word_counts(traffic["words"], int(b), shape_rng) for b in sizes]
    order = np.random.default_rng(np.random.SeedSequence([seed, 2])).permutation(
        n_ticks
    )
    word_rng = np.random.default_rng(np.random.SeedSequence([seed, 3, pass_number]))
    vocab = vocabulary(int(traffic["vocabulary"]), int(traffic["shape_seed"]))
    ticks = []
    for t in order:
        ids = word_rng.integers(0, len(vocab), size=int(counts[t].sum()))
        words = vocab[ids]
        start, texts = 0, []
        for n in counts[t]:
            texts.append(" ".join(words[start : start + n]))
            start += n
        ticks.append(texts)
    return ticks


class TickStream:
    """Tick ``i`` of an endless replay: pass ``i // ticks`` of the mix, made
    when first asked for. ``prefill`` makes the passes a window is expected to
    need at set-up, so that none is made while the clock runs."""

    def __init__(self, traffic: dict, seed: int):
        self.traffic, self.seed = traffic, seed
        self.per_pass = int(traffic["ticks"])
        self.passes: list[list[list[str]]] = []
        self.prefill(int(traffic.get("passes", 1)))

    def prefill(self, passes: int) -> None:
        while len(self.passes) < passes:
            self.passes.append(make_ticks(self.traffic, self.seed, len(self.passes)))

    def __getitem__(self, i: int) -> list[str]:
        self.prefill(i // self.per_pass + 1)
        return self.passes[i // self.per_pass][i % self.per_pass]

    def first_pass(self) -> list[list[str]]:
        """Every shape the stream will ever send is in its first pass."""
        return self.passes[0]


def word_count(text: str) -> int:
    return text.count(" ") + 1


def repeated_share(texts: list[str]) -> float:
    """The share of texts that an earlier text of the list already was."""
    return 1.0 - len(set(texts)) / len(texts) if texts else 0.0
