"""Batches of float32 messages for the wire cells, closed loop: batch i+1
is sent when batch i has come out of the receiver.

`tensors` are the gradient tensors of one layer, in the order a backward
pass finishes them, cut into buckets of at most `bucket_floats` values (a
tensor larger than the room left is split across buckets). One bucket is
one batch, one piece of a tensor one message; the layers cycle. A bucket as
large as the layer's tensors together sends each tensor as a message of its
own, one layer a batch.

Values are standard normal, drawn from the seed into a pool of `pool`
distinct batches that the run cycles through, so that set-up stays short.
Every seed gives the same sizes in the same order.
"""
from __future__ import annotations

from typing import List, Tuple

import numpy as np


def bucket_plan(tensors: List[Tuple[str, int]], bucket_floats: int) -> List[List[int]]:
    """Message sizes of each bucket of one layer."""
    plan: List[List[int]] = []
    cur: List[int] = []
    room = bucket_floats
    for _, size in tensors:
        left = int(size)
        while left:
            take = min(left, room)
            cur.append(take)
            left -= take
            room -= take
            if room == 0:
                plan.append(cur)
                cur, room = [], bucket_floats
    if cur:
        plan.append(cur)
    return plan


class Batches:
    def __init__(self, traffic: dict, seed: int):
        self.plan = bucket_plan([tuple(t) for t in traffic["tensors"]],
                                int(traffic["bucket_floats"]))
        rng = np.random.default_rng(np.random.SeedSequence([int(seed), 0x77]))
        self.pool_size = int(traffic.get("pool", 4))
        # one pool of flat batches per distinct batch size
        self.pools = {}
        for n in sorted({sum(p) for p in self.plan}):
            self.pools[n] = [rng.standard_normal(n, dtype=np.float32)
                             for _ in range(self.pool_size)]

    def sizes(self, i: int) -> List[int]:
        return self.plan[i % len(self.plan)]

    def flat(self, i: int) -> np.ndarray:
        """Batch i as one flat vector (its messages back to back)."""
        sizes = self.sizes(i)
        return self.pools[sum(sizes)][i % self.pool_size]

    def batch(self, i: int) -> List[np.ndarray]:
        flat = self.flat(i)
        out, off = [], 0
        for n in self.sizes(i):
            out.append(flat[off:off + n])
            off += n
        return out



def make(traffic: dict, seed: int, config: dict) -> Batches:
    return Batches(traffic, seed)
