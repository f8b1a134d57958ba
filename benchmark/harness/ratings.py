"""Ratings from a seed: (user int32, item int32, rating float32) rows, one
rating an example. Users and items are drawn independently, each by a
bounded power law in rank (density ~ r^-s on [1, size + 1), floored: the
shape `data.draw_ids` draws Criteo's ids with), a rank mapped to an id by a
fixed bijection of the id space, so that the active rows are spread over
the table; a rating is the traffic's mean plus a planted rank-r score plus
normal noise. Rows come out in random order, as `CLUSTER BY rand()` leaves
a ratings table before SGD. Vectorised numpy only; no JAX."""

from __future__ import annotations

import math
import os

import numpy as np

from .data import _mix64

_CHUNK = 32768
_GOLDEN = 0x9E3779B97F4A7C15


class RatingLaw:
    """The traffic's `ratings` group and the table sizes of the
    configuration."""

    def __init__(self, law: dict, users: int, items: int):
        self.users, self.items = int(users), int(items)
        self.user_s = float(law["user_exponent"])
        self.item_s = float(law["item_exponent"])
        self.rank = int(law["planted_rank"])
        self.mean = float(law["mean"])
        self.noise_sd = float(law["noise_sd"])


def rank_share(size: int, s: float, rank: int = 1) -> float:
    """The share of draws that fall on `rank` under the bounded power law
    over `size` ranks (what `power_ranks` draws)."""
    top = (size + 1.0) ** (1.0 - s) - 1.0
    return ((rank + 1.0) ** (1.0 - s) - rank ** (1.0 - s)) / top


def power_ranks(size: int, s: float, n: int,
                rng: np.random.Generator) -> np.ndarray:
    """[n] int64 ranks in [1, size]: one uniform draw and one power each."""
    u = rng.random(n, dtype=np.float32).astype(np.float64)
    top = (size + 1.0) ** (1.0 - s)
    rank = np.power(1.0 + u * (top - 1.0), 1.0 / (1.0 - s))
    return np.minimum(np.floor(rank), size).astype(np.int64)


def spreader(size: int) -> int:
    """The multiplier of the id bijection rank -> (rank - 1) * m mod size:
    the first number from 0.618 size up that shares no factor with it."""
    m = int(size * 0.6180339887) | 1
    while math.gcd(m, size) != 1:
        m += 2
    return m


def ids_of(ranks: np.ndarray, size: int) -> np.ndarray:
    return ((ranks - 1) * spreader(size) % size).astype(np.int32)


def planted(ids: np.ndarray, rank: int, salt: int) -> np.ndarray:
    """[n, rank] float64 factors of the planted score, uniform in +-c,
    c^4 = 9 / rank, so that a score of rank products (each of variance
    (c^2 / 3)^2) has unit variance: each factor the top 24 bits of one
    hash of (id, factor, salt)."""
    c = (9.0 / rank) ** 0.25
    h = _mix64((ids.astype(np.uint64)[:, None] << np.uint64(8))
               + np.arange(rank, dtype=np.uint64)[None, :]
               + np.uint64(salt))
    u = (h >> np.uint64(40)).astype(np.float64) / float(1 << 24)
    return (2.0 * u - 1.0) * c


def make_ratings(law: RatingLaw, n: int, seed: int, threads: int = 8):
    """(user [n] int32, item [n] int32, rating [n] float32) from the seed
    alone. Chunks of rows draw from generators keyed by (seed, chunk), so
    the rows do not depend on how many threads made them."""
    from concurrent.futures import ThreadPoolExecutor
    user = np.empty(n, np.int32)
    item = np.empty(n, np.int32)
    rating = np.empty(n, np.float32)

    def chunk(k: int) -> None:
        s0, s1 = k * _CHUNK, min(n, (k + 1) * _CHUNK)
        rng = np.random.default_rng([int(seed), k, 0x4A7E])
        m = s1 - s0
        u = ids_of(power_ranks(law.users, law.user_s, m, rng), law.users)
        i = ids_of(power_ranks(law.items, law.item_s, m, rng), law.items)
        score = (planted(u, law.rank, _GOLDEN)
                 * planted(i, law.rank, _GOLDEN >> 1)).sum(1)
        user[s0:s1], item[s0:s1] = u, i
        rating[s0:s1] = law.mean + score + rng.normal(0.0, law.noise_sd, m)

    with ThreadPoolExecutor(max_workers=threads) as ex:
        list(ex.map(chunk, range(-(-n // _CHUNK))))
    return user, item, rating


def write_shards(user: np.ndarray, item: np.ndarray, rating: np.ndarray,
                 out_dir: str, rows_per_shard: int, threads: int = 8) -> int:
    """Write the ratings as Parquet shards of three scalar columns, `user`
    int32, `item` int32, `rating` float32: a warehouse table as it is.
    Returns the bytes written. The directory is emptied first."""
    import pyarrow as pa
    import pyarrow.parquet as pq
    os.makedirs(out_dir, exist_ok=True)
    for f in os.listdir(out_dir):
        os.remove(os.path.join(out_dir, f))
    n = len(user)

    def shard(k: int) -> int:
        s0, s1 = k * rows_per_shard, min(n, (k + 1) * rows_per_shard)
        path = os.path.join(out_dir, f"shard-{k:05d}.parquet")
        pq.write_table(pa.table({"user": pa.array(user[s0:s1], pa.int32()),
                                 "item": pa.array(item[s0:s1], pa.int32()),
                                 "rating": pa.array(rating[s0:s1],
                                                    pa.float32())}), path)
        return os.path.getsize(path)

    from concurrent.futures import ThreadPoolExecutor
    with ThreadPoolExecutor(max_workers=threads) as ex:
        return sum(ex.map(shard, range(-(-n // rows_per_shard))))


def row_keys(user: np.ndarray, item: np.ndarray,
             rating: np.ndarray) -> np.ndarray:
    """[n, 3] int32: a rating as `data.fingerprints` takes a row (the
    rating's bits in the third column), so that the check can find which
    generated row a decoded one is."""
    return np.stack([user, item, rating.astype(np.float32).view(np.int32)],
                    1)
