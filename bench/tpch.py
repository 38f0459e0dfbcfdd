"""Seeded TPC-H ``lineitem`` tables, drawn on the device by dbgen's rules.

The TPC-H specification (clause 4.2.3) fixes how each column is drawn:

- each order has a line count uniform on [1, 7]; ``l_linenumber`` counts
  its lines from 1, and ``l_orderkey`` is the order's sparse key (of every
  32 keys the first 8 are used);
- ``o_orderdate`` is uniform on [STARTDATE, ENDDATE - 151 days]; dates are
  int32 days since 1970-01-01;
- ``l_partkey`` is uniform on [1, 200,000 x SF]; ``l_suppkey`` is one of
  the part's four suppliers; ``l_quantity`` is uniform on [1, 50];
  ``l_extendedprice`` is the quantity times the part's retail price;
  ``l_discount`` and ``l_tax`` are uniform on [0.00, 0.10] and [0.00, 0.08];
- ``l_shipdate``, ``l_commitdate`` and ``l_receiptdate`` are the order date
  plus [1, 121], the order date plus [30, 90], and the ship date plus
  [1, 30] days; ``l_returnflag`` is R or A at random where the receipt date
  is on or before CURRENTDATE (1995-06-17), else N; ``l_linestatus`` is O
  where the ship date is after CURRENTDATE, else F;
- ``l_shipinstruct`` and ``l_shipmode`` are drawn from their lists, and
  ``l_comment`` is text of 10 to 43 characters.

A table is drawn as the configuration's ``columns`` lay it out: every
column at its dbgen width (an identifier, integer or date in 4 bytes, a
decimal as an int64 count of hundredths in 8, text in its declared bytes),
each rounded up to whole 32-bit words.  ``table`` returns the sort key and
the other columns' words: ``(key (n,) int32, payload)``, the payload a
tuple of (n,) uint32 arrays, made in one jitted call from ``(seed,
table)``.  Orders are
drawn until the configuration's ``rows`` is reached and the last order is
cut, so every seed gives the same row count.  Rows are in dbgen order, and
a row's id is its position.
"""
from __future__ import annotations

import functools

import numpy as np

CURRENTDATE = 9298  # 1995-06-17
SHIPINSTRUCT = ("DELIVER IN PERSON", "COLLECT COD", "NONE", "TAKE BACK RETURN")
SHIPMODE = ("REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB")


def words(nbytes: int) -> int:
    return -(-nbytes // 4)


def payload_words(cfg: dict, key: str) -> int:
    """32-bit words of one row's payload: every column but the key."""
    return sum(words(c["bytes"]) for name, c in cfg["columns"].items() if name != key)


def key_data(seed: int, table: int) -> np.ndarray:
    """A threefry key's two words from any whole-number seed (JAX's own
    ``key(seed)`` keeps only its low 32 bits)."""
    return np.random.default_rng([seed, table]).integers(0, 2**32, 2, dtype=np.uint32)


def _text_words(strings, nbytes: int) -> np.ndarray:
    """(words, len(strings)) little-endian words of each string, padded
    with spaces to ``nbytes`` (CHAR(n)) and with zeros to whole words."""
    out = np.zeros((len(strings), words(nbytes) * 4), np.uint8)
    for i, s in enumerate(strings):
        out[i, :nbytes] = np.frombuffer(s.ljust(nbytes).encode(), np.uint8)
    return out.view("<u4").T.copy()


@functools.lru_cache(maxsize=None)
def generator(rows: int, sf: int, parts_per_sf: int, supps_per_sf: int, lines: tuple,
              orderdate: tuple, layout: tuple, key: str):
    """The jitted ``(key data) -> (key, payload words)`` of one table shape."""
    import jax
    import jax.numpy as jnp

    # orders to draw: 48 sd above what ``rows`` lines need at SF1
    orders = int(rows / (sum(lines) / 2) * 1.02) + 64
    parts, supps = parts_per_sf * sf, supps_per_sf * sf
    instruct = jnp.asarray(_text_words(SHIPINSTRUCT, 25))
    mode = jnp.asarray(_text_words(SHIPMODE, 10))

    def u32(x):
        return jax.lax.bitcast_convert_type(x.astype(jnp.int32), jnp.uint32)

    def draw(kd):
        ks = iter(jax.random.split(jax.random.wrap_key_data(kd), 16))

        def uniform(lo, hi, n=rows):  # inclusive
            return jax.random.randint(next(ks), (n,), lo, hi + 1, jnp.int32)

        r = jnp.arange(rows, dtype=jnp.int32)
        sizes = uniform(*lines, orders)
        starts = jnp.cumsum(sizes) - sizes
        first = jnp.zeros(rows, jnp.int32).at[starts].add(1, mode="drop")
        order = jnp.cumsum(first) - 1
        linenumber = r - jax.lax.cummax(jnp.where(first > 0, r, 0)) + 1
        j = order + 1
        orderkey = ((j >> 3) << 5) | (j & 7)
        odate = uniform(*orderdate, orders)[order]
        partkey = uniform(1, parts)
        supp = uniform(0, 3)
        suppkey = (partkey + supp * (supps // 4 + (partkey - 1) // supps)) % supps + 1
        quantity = uniform(1, 50)
        retail = 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)  # cents
        shipdate = odate + uniform(1, 121)
        commitdate = odate + uniform(30, 90)
        receiptdate = shipdate + uniform(1, 30)
        ra = jnp.where(uniform(0, 1) == 0, ord("R"), ord("A"))
        comment_len = uniform(10, 43)
        text = jax.random.bits(next(ks), (words(44), rows), jnp.uint32)
        comment = []
        for w in range(words(44)):
            word = jnp.zeros(rows, jnp.uint32)
            for b in range(4):
                c = (text[w] >> (8 * b)) & 0xFF
                c = c % 27
                ch = jnp.where(c == 26, 0x20, 0x61 + c)
                ch = jnp.where(4 * w + b < comment_len, ch, 0)
                word = word | (ch.astype(jnp.uint32) << (8 * b))
            comment.append(word)
        zero = jnp.zeros(rows, jnp.uint32)

        def dec(x):  # int64 hundredths as (low, high) words; every value is positive
            return [u32(x), zero]

        cols = {
            "l_orderkey": [u32(orderkey)],
            "l_partkey": [u32(partkey)],
            "l_suppkey": [u32(suppkey)],
            "l_linenumber": [u32(linenumber)],
            "l_quantity": dec(quantity * 100),
            "l_extendedprice": dec(quantity * retail),
            "l_discount": dec(uniform(0, 10)),
            "l_tax": dec(uniform(0, 8)),
            "l_returnflag": [u32(jnp.where(receiptdate <= CURRENTDATE, ra, ord("N")))],
            "l_linestatus": [u32(jnp.where(shipdate > CURRENTDATE, ord("O"), ord("F")))],
            "l_shipdate": [u32(shipdate)],
            "l_commitdate": [u32(commitdate)],
            "l_receiptdate": [u32(receiptdate)],
            "l_shipinstruct": list(instruct[:, uniform(0, len(SHIPINSTRUCT) - 1)]),
            "l_shipmode": list(mode[:, uniform(0, len(SHIPMODE) - 1)]),
            "l_comment": comment,
        }
        for name, nbytes in layout:
            assert len(cols[name]) == words(nbytes), name
        keycol = jax.lax.bitcast_convert_type(cols[key][0], jnp.int32)
        return keycol, tuple(w for name, _ in layout if name != key for w in cols[name])

    return jax.jit(draw)


def table(cfg: dict, key: str, seed: int, t: int, device=None):
    """Table ``t`` of ``seed``: ``(key (n,) int32, payload)`` on ``device``
    (the default device where None), the payload a tuple of
    ``payload_words`` (n,) uint32 arrays."""
    import jax

    if cfg["columns"].get(key, {}).get("bytes") != 4:
        raise ValueError(f"sort key {key!r} is not a 4-byte column of {cfg['name']}")
    layout = tuple((name, c["bytes"]) for name, c in cfg["columns"].items())
    f = generator(cfg["rows"], cfg["scale_factor"], cfg["parts_per_sf"], cfg["suppliers_per_sf"],
                  tuple(cfg["lines_per_order"]), tuple(cfg["o_orderdate_days"]), layout, key)
    kd = key_data(seed, t)
    return f(jax.device_put(kd, device) if device is not None else kd)
