"""The plain reference of an ORDER BY, and the comparison that decides
``correct``.

The reference is numpy's stable argsort of the key column: the sorted keys
and, as the payload, the row ids in that order (a row's id is its
position, so the permuted ids are the permutation itself); every other
column of the output is the table's column taken at those row ids.  It
imports nothing of the program under test.

What is compared follows the guarantees the configuration states, a
stable and exact sort: keys and row ids must equal the reference's bit for
bit at every position, and the other columns word for word at positions
drawn from the seed, as many per call as the harness copies out.  Every
number compared has the limit 0.
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


def verdict(nums: dict) -> bool:
    return all(nums[k] <= lim for k, lim in LIMITS.items())


def lines(nums: dict) -> list:
    """One short line per number, with its limit."""
    return [f"{k} {nums[k]} limit {LIMITS[k]}" for k in LIMITS]
