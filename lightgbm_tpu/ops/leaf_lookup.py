"""Per-row values out of a leaf-sized table without a memory lookup.

A finished tree hands every row one number of its leaf (the leaf's id,
its shrunk output).  An XLA:TPU gather out of a 255-entry table pays
~8 ns an element (87 ms over 10.5M rows on a v5e, PERF.md PR 33); a
table of at most ``SELECT_MAX`` entries is instead compared against
entry by entry on the vector units, ``sum_l where(hit_l, table[l], 0)``,
which XLA emits as ONE reduce fusion with no ``[L, n]`` intermediate
(3 ms).  Its cost grows with the table, the gather's does not
(``num_leaves`` up to 131,072 is legal), so longer tables keep the
gather.  The choice follows the table's static length alone.

The select-sum runs on the values' int32 bit patterns: exactly one
entry is hit, every other term is an integer 0, so the result is the
gather's bit for bit (-0.0 included, which a float sum would turn
into +0.0).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

SELECT_MAX = 256


def _bits(table):
    if table.dtype == jnp.int32:
        return table
    return jax.lax.bitcast_convert_type(table, jnp.int32)


def _add_each(a, b):
    return tuple(x + y for x, y in zip(a, b))


def _select_sum(hit, tables):
    """``hit`` [L, n] holds at most one True a column; for each [L]
    table the entry of that row (0 where none), all in one pass over
    ``hit`` (a variadic reduce: one fusion, the mask computed once)."""
    terms = tuple(jnp.where(hit, _bits(t)[:, None], 0) for t in tables)
    # (a module-level combiner: the jaxpr prints the callable, and the
    # purity pins compare the text of two traces)
    sums = jax.lax.reduce(terms, (jnp.int32(0),) * len(terms), _add_each,
                          (0,))
    return tuple(
        s if t.dtype == jnp.int32
        else jax.lax.bitcast_convert_type(s, jnp.float32)
        for s, t in zip(sums, tables))


def leaf_table_lookup(table, idx):
    """``table[idx]`` for a 1-D int32 / float32 ``table`` and in-range
    int32 ``idx`` [n]."""
    L = table.shape[0]
    if L > SELECT_MAX:
        return jnp.take(table, idx)
    hit = idx[None, :] == jnp.arange(L, dtype=jnp.int32)[:, None]
    return _select_sum(hit, (table,))[0]


def leaf_of_position(seg, n, tables=()):
    """The leaf of each position in [0, n), from the finished tree's
    segment table ``seg`` [L, 2] (begin, rows), whose live segments
    tile [0, n); with it ``table[leaf]`` by position for each [L]
    ``tables`` entry, off the same in-segment mask.  A leaf slot with no
    rows selects nothing, whatever its begin."""
    L = seg.shape[0]
    begin, rows = seg[:, 0], seg[:, 1]
    if L > SELECT_MAX:
        # expand each leaf's id across its span in segment order
        order = jnp.argsort(begin).astype(jnp.int32)
        leaf_of_pos = jnp.repeat(order, rows[order],
                                 total_repeat_length=n)
        return (leaf_of_pos, *(jnp.take(t, leaf_of_pos) for t in tables))
    pos = jnp.arange(n, dtype=jnp.int32)[None, :]
    hit = (pos >= begin[:, None]) & (pos < (begin + rows)[:, None])
    return _select_sum(hit, (jnp.arange(L, dtype=jnp.int32), *tables))
