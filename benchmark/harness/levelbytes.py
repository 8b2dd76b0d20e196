"""Bytes one level of the search must move, from its shape alone.

One level of the configuration search takes a pool of ``capacity``
configurations, expands the first ``expand`` of them over every
candidate operation (``window`` pending offsets plus ``crash`` pending
indeterminate operations), and keeps the best ``capacity`` distinct
results, which takes a sort of pool and candidates together.

A configuration row is its position (1 word), its register state (1
word), its alive flag (1 word), a bit per window offset and a bit per
indeterminate operation (32 to a word). The least traffic any
implementation of that level has is:

* the pool read and the next pool written: ``2 * capacity`` rows;
* every candidate row written once: ``expand * (window + crash)`` rows;
* the sort's keys (every word of a row but the alive flag), read and
  written once over pool and candidates.

This is a floor on the bytes, so a share of the bandwidth peak computed
from it is a floor on how well the level uses the memory system. It is
kept here, apart from the system, so that it stays the same whatever
implements the level.
"""

from __future__ import annotations


def row_words(window: int, crash: int) -> int:
    return 3 + (window + 31) // 32 + (crash + 31) // 32


def level_bytes(capacity: int, window: int, expand: int, crash: int) -> int:
    words = row_words(window, crash)
    cands = min(expand, capacity) * (window + crash)
    rows = 2 * capacity + cands
    sort_words = 2 * (words - 1) * (capacity + cands)
    return 4 * (words * rows + sort_words)
