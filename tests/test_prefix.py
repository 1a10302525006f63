"""serve/prefix.py: the host-side radix prefix index, in isolation.

Pure host code — no engine, no model, no jax (the subprocess test pins
the jax-free property the same way the scheduler's does).
Handles are plain Python objects here: the index must treat them as
opaque, so anything hashable works as a stand-in for a device cache tree.
"""

import os
import subprocess
import sys

import pytest

from pytorch_distributed_training_tutorials_tpu.serve.prefix import PrefixIndex, Segment

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _idx(budget=1 << 20):
    return PrefixIndex(budget)


# ----------------------------------------------------------------------
# longest-prefix-match
# ----------------------------------------------------------------------


def test_lookup_returns_longest_prefix_match():
    idx = _idx()
    idx.insert((1, 2, 3, 4), "h4", 10)
    idx.insert((1, 2, 9), "h3", 10)
    depth, seg = idx.lookup((1, 2, 3, 4, 5, 6))
    assert depth == 4 and seg.handle == "h4"
    # diverging after (1, 2): the walk stops at depth 2 and any segment
    # in that subtree is a valid donor (content on [0, 2) is identical)
    depth, seg = idx.lookup((1, 2, 7, 8))
    assert depth == 2 and seg.handle in ("h4", "h3")


def test_lookup_caps_depth_at_query_minus_one():
    """At least one suffix token must prefill — its logits sample the
    request's first generated token — so an exact-key query still matches
    one short of its full length."""
    idx = _idx()
    idx.insert((1, 2, 3), "h", 10)
    depth, seg = idx.lookup((1, 2, 3))
    assert depth == 2 and seg.handle == "h"


def test_lookup_miss_and_min_depth():
    idx = _idx()
    idx.insert((1, 2, 3), "h", 10)
    assert idx.lookup((9, 9, 9)) is None  # no shared head at all
    # a depth-2 match is rejected under min_depth=3 (too shallow to be
    # worth a splice launch) and counted as a miss
    assert idx.lookup((1, 2, 9, 9), min_depth=3) is None
    depth, _ = idx.lookup((1, 2, 3, 9), min_depth=3)
    assert depth == 3
    assert idx.stats()["hits"] == 1 and idx.stats()["misses"] == 2


def test_match_depth_can_exceed_any_single_divergence_point():
    """The donor segment only needs to share the MATCHED depth, not its
    whole key: a segment longer than the query's shared head still
    donates (stale tail positions are overwritten/masked by the suffix
    prefill — the transformer-level fact the index leans on)."""
    idx = _idx()
    idx.insert(tuple(range(32)), "long", 10)
    depth, seg = idx.lookup((0, 1, 2, 3, 99, 98))
    assert depth == 4 and seg.handle == "long"
    assert len(seg.key) >= depth  # cache covers every reused position


def test_duplicate_insert_refreshes_not_replaces():
    idx = _idx()
    assert idx.insert((1, 2), "first", 10) is True
    assert idx.insert((1, 2), "second", 10) is False
    _, seg = idx.lookup((1, 2, 5))
    assert seg.handle == "first"  # resident copy wins
    assert idx.stats()["segments"] == 1 and idx.used_bytes == 10


# ----------------------------------------------------------------------
# refcount pinning
# ----------------------------------------------------------------------


def test_pinned_segment_never_evicted():
    idx = _idx(budget=100)
    idx.insert((1,), "a", 60)
    _, seg = idx.lookup((1, 9))
    idx.acquire(seg)  # a slot is decoding from this splice
    # no room: the only evictable candidate is pinned -> insert refuses
    assert idx.insert((2,), "b", 60) is False
    assert (1,) in idx and seg.handle == "a"
    idx.release(seg)
    # released-to-zero becomes evictable again
    assert idx.insert((2,), "b", 60) is True
    assert (1,) not in idx and seg.handle is None
    assert idx.evicted_bytes == 60


def test_release_without_acquire_raises():
    idx = _idx()
    idx.insert((1,), "a", 10)
    _, seg = idx.lookup((1, 2))
    with pytest.raises(ValueError):
        idx.release(seg)
    idx.acquire(seg)
    idx.acquire(seg)  # two slots may splice from one segment
    idx.release(seg)
    idx.release(seg)
    with pytest.raises(ValueError):
        idx.release(seg)


# ----------------------------------------------------------------------
# LRU eviction under the byte budget
# ----------------------------------------------------------------------


def test_lru_evicts_coldest_first():
    idx = _idx(budget=100)
    idx.insert((1,), "a", 40)
    idx.insert((2,), "b", 40)
    idx.lookup((1, 9))  # touch (1,): (2,) is now coldest
    idx.insert((3,), "c", 40)  # needs room -> evicts (2,)
    assert (1,) in idx and (3,) in idx and (2,) not in idx
    assert idx.used_bytes == 80 and idx.evicted_bytes == 40
    assert [s.handle for s in idx.segments()] == ["a", "c"]


def test_evict_coldest_respects_pins_and_reports(
):
    """The paged engine's pool-pressure valve (ISSUE 13): evict_coldest
    frees exactly one UNPINNED segment per call (so repeated calls
    terminate), skips pinned ones, and reports False when nothing is
    evictable."""
    idx = _idx(budget=100)
    idx.insert((1,), "a", 30)
    idx.insert((2,), "b", 30)
    _, pinned = idx.lookup((1, 9))
    idx.acquire(pinned)  # (1,) is in use by a decoding slot
    assert idx.evict_coldest() is True  # takes (2,), the coldest unpinned
    assert (1,) in idx and (2,) not in idx
    assert idx.evict_coldest() is False  # only the pinned one remains
    idx.release(pinned)
    assert idx.evict_coldest() is True
    assert idx.evict_coldest() is False  # empty index


def test_on_evict_hook_fires_with_live_handle():
    """The hook is how the paged engine returns a segment's page
    refcounts to the pool: it must see the segment BEFORE the handle is
    cleared, on every eviction path (LRU pressure and evict_coldest)."""
    seen = []
    idx = PrefixIndex(100, on_evict=lambda seg: seen.append(
        (seg.key, seg.handle)
    ))
    idx.insert((1,), "a", 60)
    idx.insert((2,), "b", 60)  # LRU-evicts (1,)
    idx.evict_coldest()  # explicit path takes (2,)
    assert seen == [((1,), "a"), ((2,), "b")]  # handles still live


def test_oversized_insert_refused_without_collateral_eviction():
    idx = _idx(budget=100)
    idx.insert((1,), "a", 40)
    assert idx.insert((2,), "huge", 200) is False
    assert (1,) in idx and idx.used_bytes == 40  # nothing evicted for it


def test_eviction_prunes_trie_paths():
    """Evicting the only segment under a branch removes the branch:
    lookups that walked it must miss, not dangle (the count-pruning
    invariant _first_segment relies on)."""
    idx = _idx(budget=100)
    idx.insert((1, 2, 3), "a", 60)
    idx.insert((7, 8), "b", 40)
    idx.insert((9,), "c", 50)  # evicts coldest: (1, 2, 3)
    assert idx.lookup((1, 2, 3, 4)) is None
    depth, seg = idx.lookup((7, 8, 1))
    assert depth == 2 and seg.handle == "b"
    assert idx.stats()["segments"] == 2


def test_shared_prefix_keys_coexist_and_deepen_matches():
    """Insert-on-prefill naturally builds nested keys (multi-turn: each
    turn's prompt extends the last). The trie keeps them all; a query
    matches the deepest one it shares."""
    idx = _idx()
    idx.insert((1, 2), "turn1", 10)
    idx.insert((1, 2, 3, 4), "turn2", 10)
    idx.insert((1, 2, 3, 4, 5, 6), "turn3", 10)
    depth, seg = idx.lookup((1, 2, 3, 4, 5, 6, 7, 8))
    assert depth == 6 and seg.handle == "turn3"
    depth, seg = idx.lookup((1, 2, 3, 9))
    assert depth == 3 and seg.handle in ("turn2", "turn3")
    assert len(idx) == 3


# ----------------------------------------------------------------------
# hygiene
# ----------------------------------------------------------------------


def test_bad_constructions_raise():
    with pytest.raises(ValueError):
        PrefixIndex(0)
    idx = _idx()
    with pytest.raises(ValueError):
        idx.insert((), "h", 10)


def test_segment_repr_is_cheap():
    seg = Segment((1, 2, 3), object(), 123)
    assert "len=3" in repr(seg) and "123" in repr(seg)


def test_prefix_module_imports_no_jax():
    """The runtime half of the host-only contract (CLAUDE.md serving
    invariants): scheduling/index decisions must never initialize a
    backend. The module list is SINGLE-SOURCED from
    analysis/hostonly.py — the same declaration graftcheck's
    jax-free-host rule enforces statically over the import graph, so the
    runtime pin and the static rule can never drift. (The import is
    jax-free itself: analysis/ is pure stdlib.)"""
    from pytorch_distributed_training_tutorials_tpu.analysis.hostonly import (
        HOST_ONLY_MODULES,
    )

    code = (
        "import sys\n"
        + "".join(f"import {m}\n" for m in HOST_ONLY_MODULES)
        + "assert 'jax' not in sys.modules, "
          "'host-only modules must not import jax'\n"
    )
    env = {k: v for k, v in os.environ.items() if k != "PYTHONSTARTUP"}
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=120, cwd=REPO, env=env,
    )
    assert out.returncode == 0, out.stderr


if __name__ == "__main__":
    sys.exit(pytest.main([__file__, "-q"]))
