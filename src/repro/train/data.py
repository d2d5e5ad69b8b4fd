"""Synthetic LM data pipeline with background host prefetch.

Produces next-token-prediction batches from a deterministic synthetic corpus
(a mixture of Zipfian unigrams and repeated n-gram motifs so a real model
exhibits a real learning curve), double-buffered on a worker thread.
"""
from __future__ import annotations

import queue
import threading
from typing import Any, Dict, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig


class SyntheticCorpus:
    """Deterministic pseudo-corpus: Zipf unigrams + injected repeating motifs."""

    def __init__(self, vocab: int, seed: int = 0, motif_len: int = 16, n_motifs: int = 64):
        self.vocab = vocab
        self.rng = np.random.default_rng(seed)
        ranks = np.arange(1, vocab + 1, dtype=np.float64)
        probs = 1.0 / ranks ** 1.1
        self.probs = probs / probs.sum()
        self.motifs = self.rng.integers(0, vocab, (n_motifs, motif_len))

    def sample(self, batch: int, seq_len: int) -> np.ndarray:
        toks = self.rng.choice(self.vocab, size=(batch, seq_len + 1), p=self.probs)
        # splice motifs so there is learnable structure
        n_splice = max(1, seq_len // 64)
        for b in range(batch):
            for _ in range(n_splice):
                m = self.motifs[self.rng.integers(0, len(self.motifs))]
                start = self.rng.integers(0, seq_len + 1 - len(m))
                toks[b, start : start + len(m)] = m
        return toks.astype(np.int32)


class DataLoader:
    """Background-thread prefetching loader yielding model-ready batches."""

    def __init__(
        self,
        cfg: ModelConfig,
        batch: int,
        seq_len: int,
        seed: int = 0,
        prefetch: int = 2,
        sharding: Optional[Any] = None,
    ):
        self.cfg = cfg
        self.batch = batch
        self.seq_len = seq_len
        self.corpus = SyntheticCorpus(cfg.vocab, seed)
        self.sharding = sharding
        self._q: "queue.Queue" = queue.Queue(maxsize=prefetch)
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._worker, daemon=True)
        self._thread.start()

    def _make(self) -> Dict[str, Any]:
        """Hidden length S = n_prefix + T; tokens: (B,T); labels/mask: (B,S)."""
        cfg = self.cfg
        t = self.seq_len - cfg.n_prefix
        toks = self.corpus.sample(self.batch, t)              # (B, T+1)
        prefix_zeros = np.zeros((self.batch, cfg.n_prefix), np.int32)
        batch: Dict[str, Any] = {
            "tokens": toks[:, :t],
            "labels": np.concatenate([prefix_zeros, toks[:, 1 : t + 1]], axis=1),
        }
        mask = np.ones((self.batch, self.seq_len), np.float32)
        if cfg.n_prefix:
            mask[:, : cfg.n_prefix] = 0.0
            batch["prefix_embeds"] = np.asarray(
                self.corpus.rng.normal(0, 0.02, (self.batch, cfg.n_prefix, cfg.d_model)),
                np.float32,
            )
        batch["mask"] = mask
        return batch

    def _worker(self) -> None:
        # a batch that met a full queue is offered again, never replaced:
        # the stream must not depend on how slowly the consumer drains it
        b = None
        while not self._stop.is_set():
            if b is None:
                b = self._make()
            try:
                self._q.put(b, timeout=1.0)
            except queue.Full:
                continue
            b = None

    def __iter__(self) -> Iterator[Dict[str, Any]]:
        return self

    def __next__(self) -> Dict[str, Any]:
        host = self._q.get()
        if self.sharding is not None:
            return jax.tree.map(
                lambda a, s: jax.device_put(a, s), host, self.sharding
            )
        return jax.tree.map(jnp.asarray, host)

    def close(self) -> None:
        self._stop.set()
