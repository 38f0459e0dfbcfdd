"""The plain reference of an ORDER BY, and the comparison that decides
``correct``.

The reference is numpy's stable argsort of the key column: the sorted keys
and, as the payload, the row ids in that order (a row's id is its
position, so the permuted ids are the permutation itself); every other
column of the output is the table's column taken at those row ids.  It
imports nothing of the program under test.

What is compared follows the guarantees the configuration states.  A
stable and exact sort (``compare``, ``LIMITS``): keys and row ids must
equal the reference's bit for bit at every position, and the other columns
word for word at positions drawn from the seed, as many per call as the
harness copies out.  An exact sort that keeps no tie order and returns
padded shards, as ``repro.dist.sort`` does (``compare_shards``,
``SHARD_LIMITS``): the shards' valid rows, joined in shard order, must hold
the sorted keys bit for bit at every position, and every row of the table
once and whole: the row ids a permutation of the table's, each key the
table's key at its row id, the other columns word for word the table's at
that row id at the sampled positions; and no shard may report an overflow.
Every number compared has the limit 0.
"""
from __future__ import annotations

import numpy as np

# name -> limit; each number is compared as "value <= limit"
LIMITS = {
    "wrong_keys": 0,          # output positions whose key differs from the reference
    "wrong_rowids": 0,        # output positions whose row id differs from the reference
    "missing_rows": 0,        # |rows returned - rows in the table|, over all checked calls
    "wrong_column_words": 0,  # words of the other columns, at the sampled positions, that differ
    "unchecked_calls": 0,     # calls of the window whose output was not compared
}

# name -> limit of ``compare_shards``, for a configuration whose sort keeps
# no tie order; each number is compared as "value <= limit"
SHARD_LIMITS = {
    "wrong_keys": 0,            # joined positions whose key differs from the sorted keys
    "ids_not_once": 0,          # table rows whose id the output holds other than once, plus ids out of range
    "keys_off_their_row": 0,    # joined positions whose key is not the table's key at the row id beside it
    "missing_rows": 0,          # |valid rows returned - rows in the table|, over all checked calls
    "wrong_column_words": 0,    # words of the other columns, at the sampled positions, that differ
    "overflow_shards": 0,       # shards that raised the overflow flag
    "unchecked_calls": 0,       # calls of the window whose output was not compared
}


def expected(keys: np.ndarray):
    """(sorted keys, row ids in sorted order) of a table whose row ids are
    its positions."""
    perm = np.argsort(keys, kind="stable").astype(np.int32)
    return keys[perm], perm


def compare(outputs, tables, columns_at, calls: int):
    """(numbers of ``LIMITS`` over every call's host copy, calls whose
    output broke a limit or never came).

    ``outputs`` lists ``(table index, keys, rowids, positions, columns)``,
    one entry per call of the window: the call's keys and row ids whole,
    and its other columns' words at the sampled ``positions``, as a
    (words, positions) array.  ``tables`` holds each table's key column;
    ``columns_at(t, rows)`` reads table ``t``'s other columns at ``rows``
    in the same shape.  A call whose output has another length than its
    table counts every row as wrong and the difference as missing."""
    nums = dict.fromkeys(LIMITS, 0)
    nums["unchecked_calls"] = calls - len(outputs)
    failed = nums["unchecked_calls"]
    want = {}
    for t, keys, rowids, pos, cols in outputs:
        if t not in want:
            want[t] = expected(tables[t])
        wk, wr = want[t]
        one = {}
        if keys.shape != wk.shape or rowids.shape != wr.shape:
            one["missing_rows"] = abs(wk.shape[0] - min(keys.shape[0], rowids.shape[0]))
            one["wrong_keys"], one["wrong_rowids"] = wk.shape[0], wr.shape[0]
            one["wrong_column_words"] = cols.size
        else:
            one["wrong_keys"] = int(np.count_nonzero(keys != wk))
            one["wrong_rowids"] = int(np.count_nonzero(rowids != wr))
            ref = columns_at(t, wr[pos])
            one["wrong_column_words"] = (int(np.count_nonzero(cols != ref))
                                         if cols.shape == ref.shape else ref.size)
        for k, v in one.items():
            nums[k] += v
        failed += any(one.values())
    return nums, failed


def join(shards: np.ndarray, counts: np.ndarray) -> np.ndarray:
    """The first ``counts[i]`` entries of each row of ``shards``, in order."""
    return np.concatenate([s[:max(0, int(c))] for s, c in zip(shards, counts)])


def compare_shards(outputs, tables, columns_at, calls: int):
    """(numbers of ``SHARD_LIMITS`` over every call's host copy, calls whose
    output broke a limit or never came).

    ``outputs`` lists ``(table index, keys, rowids, counts, overflow,
    positions, columns)``, one entry per call of the window: keys and row
    ids as (shards, capacity) arrays, each shard's valid count and overflow
    flag, and the other columns' words at the sampled ``positions`` of the
    joined output, as a (words, positions) array.  ``tables`` and
    ``columns_at`` as for ``compare``.  Where the joined output has another
    length than its table, every key counts as wrong and the difference as
    missing; a sampled position past its end counts every word as wrong."""
    nums = dict.fromkeys(SHARD_LIMITS, 0)
    nums["unchecked_calls"] = calls - len(outputs)
    failed = nums["unchecked_calls"]
    want = {}
    for t, keys, rowids, counts, overflow, pos, cols in outputs:
        table = tables[t]
        if t not in want:
            want[t] = np.sort(table)
        n = table.shape[0]
        k, r = join(keys, counts), join(rowids, counts)
        ok = (r >= 0) & (r < n)
        at = np.where(ok, r, 0)
        one = {
            "wrong_keys": int(np.count_nonzero(k != want[t])) if k.shape == want[t].shape else n,
            "ids_not_once": int(np.count_nonzero(np.bincount(r[ok], minlength=n) != 1)
                                + np.count_nonzero(~ok)),
            "keys_off_their_row": int(np.count_nonzero(~ok | (k != table[at]))),
            "missing_rows": abs(n - k.shape[0]),
            "overflow_shards": int(np.count_nonzero(overflow)),
        }
        past = np.where(pos < r.shape[0], pos, r.shape[0])  # past the end: a row that is not sound
        rows, sound = np.append(at, 0)[past], np.append(ok, False)[past]
        ref = columns_at(t, rows.astype(np.int32))
        one["wrong_column_words"] = (int(np.count_nonzero((cols != ref) | ~sound))
                                     if cols.shape == ref.shape else ref.size)
        for name, v in one.items():
            nums[name] += v
        failed += any(one.values())
    return nums, failed


def verdict(nums: dict, limits: dict = LIMITS) -> bool:
    return all(nums[k] <= lim for k, lim in limits.items())
