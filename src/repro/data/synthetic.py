"""Synthetic corpora with paper-matched statistics (DESIGN §7 scale note).

- Zipf LM: a latent-cluster bigram language — context determines a cluster of
  plausible next tokens (so adaptive samplers have structure to exploit) with
  a Zipf marginal (so unigram beats uniform, as in the paper).
- RecSys: latent-factor user/item interactions (SASRec/GRU4Rec task shape).
- XMC: sparse BOW features with clustered label embeddings.
All generators are deterministic in their seed.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class ZipfLM:
    vocab_size: int
    num_clusters: int
    seq_len: int
    zipf_a: float = 1.2
    within_cluster_noise: float = 0.15
    seed: int = 0

    def _tables(self):
        rng = np.random.default_rng(self.seed)
        v, c = self.vocab_size, self.num_clusters
        token_cluster = rng.integers(0, c, size=v)
        # cluster transition matrix (sparse-ish, row-stochastic)
        trans = rng.dirichlet(np.ones(c) * 0.3, size=c)
        # zipf marginal over tokens, renormalized within cluster
        ranks = np.arange(1, v + 1)
        zipf = ranks ** (-self.zipf_a)
        rng.shuffle(zipf)
        within = np.zeros((c, v))
        for k in range(c):
            m = token_cluster == k
            w = zipf * m
            if w.sum() == 0:
                # cluster with no assigned tokens (small vocab / many
                # clusters): fall back to the global marginal so the row
                # stays stochastic instead of dividing to NaN
                w = m.astype(float) if m.any() else zipf.copy()
            within[k] = w / w.sum()
        return token_cluster, trans, within, zipf / zipf.sum()

    def sample(self, num_seqs: int, seed: int | None = None) -> np.ndarray:
        """Returns int32 [num_seqs, seq_len].

        Each draw is `rng.choice(n, p=row)` done by hand: one uniform from
        the stream, looked up in the row's normalised CDF — the same
        numbers numpy's choice gives, in O(log V) per draw instead of the
        O(V) CDF build per call (which made a 50k vocabulary take
        minutes)."""
        token_cluster, trans, within, marginal = self._tables()
        rng = np.random.default_rng(self.seed + 1 if seed is None else seed)
        c = self.num_clusters
        within_cdf, trans_cdf, marginal_cdf = map(_cdf, (within, trans,
                                                         marginal))
        out = np.empty((num_seqs, self.seq_len), np.int32)
        cur = rng.integers(0, c, size=num_seqs)
        for t in range(self.seq_len):
            # mostly stay coherent with the cluster chain, sometimes noise
            noise = rng.random(num_seqs) < self.within_cluster_noise
            tok_coherent = _draw_rows(within_cdf, cur, rng.random(num_seqs))
            tok_noise = marginal_cdf.searchsorted(rng.random(num_seqs),
                                                  side="right")
            tok = np.where(noise, tok_noise, tok_coherent)
            out[:, t] = tok
            cur = _draw_rows(trans_cdf, token_cluster[tok],
                             rng.random(num_seqs))
        return out

    def unigram_counts(self, tokens: np.ndarray) -> np.ndarray:
        return np.bincount(tokens.reshape(-1), minlength=self.vocab_size)


def _cdf(p: np.ndarray) -> np.ndarray:
    """Row-wise CDF normalised as numpy's `Generator.choice` does it."""
    cdf = p.cumsum(axis=-1)
    return cdf / cdf[..., -1:]


def _draw_rows(cdf: np.ndarray, rows: np.ndarray, u: np.ndarray):
    """Draw i looks up uniform u[i] in CDF row rows[i]."""
    out = np.empty(len(rows), np.int64)
    for r in np.unique(rows):
        m = rows == r
        out[m] = cdf[r].searchsorted(u[m], side="right")
    return out


def zipf_tokens(num_seqs: int, seq_len: int, vocab: int, a: float = 1.2,
                seed: int = 0) -> np.ndarray:
    """Fast i.i.d. Zipf token stream (for throughput-oriented benchmarks)."""
    rng = np.random.default_rng(seed)
    ranks = np.arange(1, vocab + 1)
    p = ranks ** (-a)
    p /= p.sum()
    perm = rng.permutation(vocab)
    toks = rng.choice(vocab, p=p, size=(num_seqs, seq_len))
    return perm[toks].astype(np.int32)


def recsys_interactions(num_users: int, num_items: int, seq_len: int,
                        d_latent: int = 16, seed: int = 0) -> np.ndarray:
    """User behaviour sequences from a latent-factor model. [U, seq_len] int32."""
    rng = np.random.default_rng(seed)
    users = rng.normal(size=(num_users, d_latent))
    items = rng.normal(size=(num_items, d_latent))
    # session drift: user vector takes a small random walk per step
    out = np.empty((num_users, seq_len), np.int32)
    for t in range(seq_len):
        scores = users @ items.T + rng.gumbel(size=(num_users, num_items)) * 2.0
        out[:, t] = scores.argmax(-1)
        users = users + 0.15 * rng.normal(size=users.shape)
    return out


def xmc_dataset(num_samples: int, num_labels: int, feat_dim: int,
                nnz: int = 20, num_clusters: int = 32, seed: int = 0):
    """Sparse BOW features + clustered labels.

    Returns (features [S, feat_dim] float32 dense-ified, labels [S] int32).
    """
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(num_clusters, feat_dim)) * 2.0
    label_cluster = rng.integers(0, num_clusters, size=num_labels)
    label_vecs = centers[label_cluster] + 0.3 * rng.normal(size=(num_labels, feat_dim))
    labels = rng.integers(0, num_labels, size=num_samples)
    feats = label_vecs[labels] + 0.5 * rng.normal(size=(num_samples, feat_dim))
    # sparsify: keep top-|nnz| magnitude dims per sample
    idx = np.argsort(-np.abs(feats), axis=1)[:, nnz:]
    np.put_along_axis(feats, idx, 0.0, axis=1)
    return feats.astype(np.float32), labels.astype(np.int32)
