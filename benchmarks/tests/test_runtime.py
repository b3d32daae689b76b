"""What a window's ``compiles_in_window`` counts, and what a replay repeats."""

import json
import subprocess
import sys

from benchmarks.harness.loader import ROOT, load_cell
from benchmarks.harness.traffic import TickStream, repeated_share, word_count

COUNT_A_CACHE_HIT = """
import sys, jax, jax.numpy as jnp
sys.path.insert(0, {root!r})
from benchmarks.harness.runtime import CompileCounter
jax.config.update("jax_compilation_cache_dir", sys.argv[1])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
hits = []
jax.monitoring.register_event_listener(lambda e, **kw: hits.append(e) if e.endswith("cache_hits") else None)
counter = CompileCounter()
f = lambda x: jnp.tanh(x @ x.T).sum()
x = jnp.ones((8, 8))
jax.jit(f)(x).block_until_ready()
compiled = counter.count
jax.clear_caches()
jax.jit(f)(x).block_until_ready()  # found in the persistent cache, not compiled
print(compiled, counter.count - compiled, len(hits))
"""


def test_a_program_loaded_from_the_persistent_cache_counts_as_built(tmp_path):
    done = subprocess.run(
        [sys.executable, "-c", COUNT_A_CACHE_HIT.format(root=ROOT), str(tmp_path)],
        capture_output=True, text=True, timeout=120, env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"},
    )
    assert done.returncode == 0, done.stderr
    compiled, loaded, hits = map(int, done.stdout.split())
    assert compiled >= 1 and hits >= 1  # the second call did hit the cache
    assert loaded >= hits  # and the counter saw each hit


def test_a_later_pass_has_the_same_shapes_and_other_words():
    traffic = load_cell("minilm-l6-384.retrieve").traffic
    stream = TickStream(traffic, seed=2147483659)
    per_pass = int(traffic["ticks"])
    first = [stream[i] for i in range(per_pass)]
    second = [stream[per_pass + i] for i in range(per_pass)]
    shape = lambda ticks: [[word_count(t) for t in tick] for tick in ticks]
    assert shape(first) == shape(second)
    texts = [t for tick in first + second for t in tick]
    assert repeated_share(texts) < 0.01  # only one- and two-word queries can meet again
    assert abs(repeated_share(["a b", "a b", "c"]) - 1 / 3) < 1e-12


def test_the_mixes_have_the_means_their_sources_state():
    for cell in ("minilm-l6-384.retrieve", "bge-base-768.ingest"):
        traffic = load_cell(cell).traffic
        words = [word_count(t) for tick in TickStream(traffic, 7).first_pass() for t in tick]
        assert abs(sum(words) / len(words) / traffic["words"]["mean"] - 1) < 0.03
        assert json.dumps(traffic["sources"])  # every mix says where its numbers come from


def test_rungs_get_their_stated_share():
    traffic = load_cell("minilm-l6-384.retrieve").traffic
    sizes = [len(t) for t in TickStream(traffic, 7).first_pass()]
    rungs = traffic["tick_size"]["sizes"]
    assert sorted(set(sizes)) == rungs
    assert {sizes.count(b) for b in rungs} == {len(sizes) // len(rungs)}
