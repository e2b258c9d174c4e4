"""Property test for the shared chunk/page ``relevant`` predicate.

``decode_common.chunk_relevant`` gates whole KV units (dense chunks, pool
pages) in *both* decode kernels and both their split-K variants: a False
must mean "no position in this unit survives the mask" (soundness — a
false skip silently corrupts the softmax) and a True must mean at least
one position survives (completeness — a false admit only wastes compute,
but the predicate is exact and we pin that). Hypothesis drives windows
smaller than / equal to / straddling the unit, plus the length-0 and
full-cache edges.
"""

import numpy as np
import pytest

try:  # dev-only dep (requirements-dev.txt); the exhaustive sweep below
    from hypothesis import given, settings, strategies as st  # still runs without it
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from repro.kernels import decode_common
from repro.kernels.decode_common import chunk_relevant


def _valid_positions(chunk_start, chunk_len, length, window):
    """Ground truth: the decode mask evaluated per position."""
    pos = np.arange(chunk_start, chunk_start + chunk_len)
    valid = pos < length
    if window is not None and window > 0:
        valid &= pos > length - 1 - window
    return valid


def _check_exact(chunk_start, chunk_len, length, window):
    rel = bool(chunk_relevant(chunk_start, chunk_len, length, window))
    truth = bool(_valid_positions(chunk_start, chunk_len, length, window).any())
    assert rel == truth, (
        f"start={chunk_start} len={chunk_len} length={length} window={window}"
    )


def test_chunk_relevant_exhaustive_small_domain():
    """Every (unit index, length, window) over a small cache: the
    predicate equals per-position ground truth — including windows
    smaller than, equal to, and straddling the unit, and length 0."""
    chunk_len = 8
    for chunk_idx in range(8):
        for length in range(0, 65, 3):
            for window in (None, 1, 4, 7, 8, 9, 20, 64, 100):
                _check_exact(chunk_idx * chunk_len, chunk_len, length, window)


if HAVE_HYPOTHESIS:
    @settings(max_examples=300, deadline=None)
    @given(
        chunk_len=st.sampled_from([8, 16, 128, 512]),
        chunk_idx=st.integers(min_value=0, max_value=64),
        length=st.integers(min_value=0, max_value=4096),
        window=st.one_of(
            st.none(),
            st.integers(min_value=1, max_value=4096),
        ),
    )
    def test_chunk_relevant_is_exact(chunk_len, chunk_idx, length, window):
        _check_exact(chunk_idx * chunk_len, chunk_len, length, window)


@pytest.mark.parametrize("window", [8, 128, 200])
def test_window_vs_chunk_edges(window):
    """Window smaller than / equal to / straddling a 128-wide chunk: the
    single chunk holding the window's left edge must be admitted, chunks
    entirely behind it must not."""
    chunk = 128
    length = 1000  # window covers [length-window, length-1]
    for idx in range(0, 10):
        start = idx * chunk
        rel = bool(chunk_relevant(start, chunk, length, window))
        truth = bool(_valid_positions(start, chunk, length, window).any())
        assert rel == truth, (idx, window)
    # the chunk straddling the left edge specifically
    lo = length - window
    idx = lo // chunk
    assert bool(chunk_relevant(idx * chunk, chunk, length, window))
    if idx > 0:
        assert not bool(chunk_relevant((idx - 1) * chunk, chunk, length, window))


def test_length_zero_admits_nothing():
    for start in (0, 128, 512):
        assert not bool(chunk_relevant(start, 128, 0, None))
        assert not bool(chunk_relevant(start, 128, 0, 64))


def test_both_decode_kernels_share_the_predicate():
    """The dense and paged kernels (one-pass and split-K paths alike) must
    gate units through decode_common.chunk_relevant and merge partials via
    combine_split_states, not re-derive either locally. Single
    implementation: the linter's ``decode-relevance-shared`` rule."""
    from repro.analysis import run_rules

    assert run_rules(rules=["decode-relevance-shared"]) == []


@pytest.mark.parametrize("block_len", [16, 48, 256])
@pytest.mark.parametrize("window", [None, 1, 37, 4096])
def test_live_block_range_is_the_relevant_blocks(block_len, window):
    """The paged walk's ``[first, last)`` is exactly the set of blocks the
    relevance predicate admits, for every length up to a few blocks."""
    for length in range(0, 5 * block_len + 3):
        first, last = (int(x) for x in decode_common.live_block_range(
            length, window, block_len))
        relevant = [i for i in range(8) if decode_common.chunk_relevant(
            i * block_len, block_len, length, window)]
        assert list(range(first, last)) == relevant
