"""Token traffic for training cells, generated from ``--seed``.

A copy of the token generator of the trainer's ``data/pipeline.py``
(``SyntheticStream``): a Zipf marginal clipped to the vocabulary, with one
short motif repeated at a random offset in each row. It lives here so that
no change to the program can move the traffic. Row ``r`` of step ``n``
depends only on ``(seed, n, r)``, so any step's batch can be made again,
by the reference or on another host.
"""

from __future__ import annotations

import numpy as np

GENERATORS = ("zipf_motif",)


def check(traffic: dict) -> None:
    if traffic.get("generator") not in GENERATORS:
        raise ValueError(f"unknown traffic generator {traffic.get('generator')!r}"
                         f"; known: {GENERATORS}")


def rows(traffic: dict, vocab: int, seed: int, step: int, lo: int,
         hi: int) -> np.ndarray:
    """Rows ``[lo, hi)`` of step ``step``: int64 ``[hi - lo, seq_len + 1]``."""
    check(traffic)
    seq = int(traffic["seq_len"])
    m_lo, m_hi = traffic["motif_len"]
    out = []
    for r in range(lo, hi):
        rng = np.random.default_rng([int(seed), int(step), int(r)])
        z = rng.zipf(float(traffic["zipf_a"]), size=seq + 1).astype(np.int64)
        toks = (z % (vocab - 1)) + 1
        m_len = int(rng.integers(m_lo, m_hi))
        off = int(rng.integers(0, seq - m_len))
        toks[off:off + m_len] = toks[:m_len]
        out.append(toks)
    return np.stack(out)


def global_batch(traffic: dict, chips: int) -> int:
    return int(traffic["rows_per_chip"]) * chips


def batch_np(traffic: dict, vocab: int, chips: int, seed: int,
             step: int) -> dict:
    """The whole batch of one step as ``tokens`` and ``labels`` (int32)."""
    t = rows(traffic, vocab, seed, step, 0, global_batch(traffic, chips))
    return {"tokens": t[:, :-1].astype(np.int32),
            "labels": t[:, 1:].astype(np.int32)}


class Stream:
    """The trainer loop's ``stream``: each step's batch, placed shard by
    shard on the step's batch shardings (no host holds rows it does not
    feed)."""

    def __init__(self, traffic: dict, vocab: int, chips: int, seed: int,
                 shardings: dict):
        self.traffic, self.vocab, self.seed = traffic, vocab, seed
        self.B = global_batch(traffic, chips)
        self.S = int(traffic["seq_len"])
        self.shardings = shardings

    def sharded_batch(self, step, mesh, sharding):
        import jax

        out = {}
        for name in ("tokens", "labels"):
            def cb(index, name=name):
                rs = index[0]
                lo = rs.start or 0
                hi = rs.stop if rs.stop is not None else self.B
                t = rows(self.traffic, self.vocab, self.seed, step, lo, hi)
                t = t[:, :-1] if name == "tokens" else t[:, 1:]
                col = index[1] if len(index) > 1 else slice(None)
                return t[:, col].astype(np.int32)

            out[name] = jax.make_array_from_callback(
                (self.B, self.S), self.shardings[name], cb)
        return out
