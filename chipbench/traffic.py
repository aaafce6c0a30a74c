"""Token traffic for the training cells, and its data-source entry.

The generator is a copy of the repo's ``synthetic_lm`` source
(``repro/data/pipeline.py:SyntheticLM``): a sticky Markov chain over token
clusters, each cluster a Zipf distribution over a permutation of the
vocabulary. It is kept here so that a change to the program's data layer
cannot move the yardstick. Two things differ from the original:

* the whole pool of batches is drawn at once from one stream seeded by
  ``--seed`` and vectorised over rows (one Python loop over positions),
  which is what makes pre-generating a run's traffic cheap;
* a cluster's token is drawn as ``perm_c[r]`` with ``r`` the Zipf rank,
  which is the same distribution as the original's search over the
  permuted probabilities.

A run draws ``pool_steps`` distinct global batches during set-up and the
trainer cycles through them; every batch of the first ``pool_steps`` steps
is new.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List

import numpy as np

_STREAM = 0xC81B


def _cluster_tables(vocab: int, seed: int, num_clusters: int,
                    stickiness: float):
    """(transition CDF (C, C), token permutation per cluster (C, V), Zipf
    rank CDF (V,)) — the same tables as ``SyntheticLM.__init__``."""
    root = np.random.default_rng(seed)
    C = num_clusters
    trans = root.random((C, C)) + np.eye(C) * (
        stickiness * C / (1 - stickiness + 1e-9))
    trans = trans / trans.sum(1, keepdims=True)
    perms = np.stack([np.random.default_rng(seed * 1000 + c).permutation(vocab)
                      for c in range(C)]).astype(np.int32)
    zipf = 1.0 / np.arange(1, vocab + 1, dtype=np.float64)
    return np.cumsum(trans, axis=1), perms, np.cumsum(zipf / zipf.sum())


def make_pool(vocab: int, seq_len: int, rows: int, steps: int, seed: int,
              num_clusters: int = 16, stickiness: float = 0.8
              ) -> Dict[str, np.ndarray]:
    """``steps`` batches of ``rows`` sequences: ``tokens``/``labels`` of
    shape (steps, rows, seq_len), int32, labels the tokens shifted by one."""
    trans_cdf, perms, zipf_cdf = _cluster_tables(vocab, seed, num_clusters,
                                                 stickiness)
    g = np.random.default_rng((seed, _STREAM))
    n, T = steps * rows, seq_len + 1
    u_tok = g.random((n, T))
    u_cl = g.random((n, T))
    c = g.integers(num_clusters, size=n)
    clusters = np.empty((n, T), np.int32)
    for t in range(T):
        clusters[:, t] = c
        c = np.minimum((trans_cdf[c] < u_cl[:, t, None]).sum(1),
                       num_clusters - 1)
    ranks = np.minimum(np.searchsorted(zipf_cdf, u_tok), vocab - 1)
    tokens = perms[clusters, ranks].reshape(steps, rows, T)
    return {"tokens": np.ascontiguousarray(tokens[..., :-1]),
            "labels": np.ascontiguousarray(tokens[..., 1:])}


# ---------------------------------------------------------------------------
# data-source entry: the trainer reads the pool through its registry
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class PoolConfig:
    """The ``data`` section of a cell's experiment."""
    vocab_size: int = 1024
    seq_len: int = 128
    global_batch: int = 16
    seed: int = 0
    pool_steps: int = 8
    num_clusters: int = 16
    cluster_stickiness: float = 0.8
    num_hosts: int = 1
    host_index: int = 0


SOURCE_NAME = "chipbench_pool"


def register() -> None:
    """Add the pool to the program's data-source registry (idempotent)."""
    from repro.data import sources
    from repro.data.pipeline import ArraySpec, DataSourceBase

    class PoolSource(DataSourceBase):
        def __init__(self, cfg: PoolConfig):
            super().__init__()
            if cfg.num_hosts != 1:
                raise ValueError("the benchmark runs in one process")
            self.cfg = cfg
            self.pool = make_pool(cfg.vocab_size, cfg.seq_len,
                                  cfg.global_batch, cfg.pool_steps, cfg.seed,
                                  cfg.num_clusters, cfg.cluster_stickiness)

        def spec(self):
            shape = (self.cfg.global_batch, self.cfg.seq_len)
            return {k: ArraySpec(shape, np.dtype(np.int32))
                    for k in ("tokens", "labels")}

        def batch_at(self, step: int) -> Dict[str, np.ndarray]:
            i = step % self.cfg.pool_steps
            return {k: v[i] for k, v in self.pool.items()}

    def validate(d: PoolConfig, mcfg, batch: int, seq: int) -> List[str]:
        return [f"data.{k}={got} != {want}" for k, got, want in [
            ("global_batch", d.global_batch, batch),
            ("seq_len", d.seq_len, seq),
            ("vocab_size", d.vocab_size, mcfg.vocab_size)] if got != want]

    def derive(mcfg, *, batch: int, seq: int, seed: int) -> PoolConfig:
        return PoolConfig(vocab_size=mcfg.vocab_size, seq_len=seq,
                          global_batch=batch, seed=seed)

    sources.register_source(sources.SourceEntry(
        SOURCE_NAME, PoolConfig, PoolSource,
        sources.TaskAdapter(kind="lm", model_overrides=lambda d: {},
                            derive=derive, validate=validate)),
        overwrite=True)
