"""Token corpora for the benchmark's traffic, made in memory from a seed.

A copy of the program's token generator (``data/synthetic.py``): token ids
follow a Zipf law (p_k ∝ 1/(k+1)), as code-token frequencies do.  Kept here
so that the yardstick does not move when the program's generator changes.

Document lengths are lognormal and clipped; every document carries a
language label.  Labels come in runs, as repositories are ingested one at a
time, and are Zipf-skewed over the languages.  Lengths and labels come from
the traffic's own ``layout_seed``: every run seed serves the same set of
documents, in another order of repositories and with other token ids.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict

import numpy as np


@dataclass
class Corpus:
    tokens: np.ndarray      # int32, all documents back to back
    offsets: np.ndarray     # int64, document i is tokens[offsets[i]:offsets[i+1]]
    lang: np.ndarray        # int64 label per document

    def __len__(self) -> int:
        return len(self.offsets) - 1

    def doc(self, i: int) -> np.ndarray:
        return self.tokens[self.offsets[i]:self.offsets[i + 1]]

    @property
    def lengths(self) -> np.ndarray:
        return np.diff(self.offsets)


def zipf_ids(rng: np.random.Generator, n: int, vocab: int) -> np.ndarray:
    cdf = np.cumsum(1.0 / np.arange(1, vocab + 1))
    cdf /= cdf[-1]
    return np.searchsorted(cdf, rng.random(n), side="right").astype(np.int32)


def layout(spec: Dict[str, Any]):
    """(lengths, labels, repository of each document) of a corpus spec."""
    rng = np.random.default_rng(spec["layout_seed"])
    want = int(spec["total_tokens"])
    lo, hi = int(spec["min_tokens"]), int(spec["max_tokens"])
    median, sigma = float(spec["median_tokens"]), float(spec["sigma"])
    mean = median * np.exp(sigma ** 2 / 2)
    n = max(16, int(2 * want / max(min(mean, hi), lo)))
    lens = np.clip(np.round(np.exp(np.log(median) + sigma
                                   * rng.standard_normal(n))), lo, hi)
    lens = lens.astype(np.int64)
    n_docs = int(np.searchsorted(np.cumsum(lens), want)) + 1
    if n_docs > n:
        raise ValueError("corpus layout drew too few documents")
    lens = lens[:n_docs]
    langs = int(spec.get("langs", 1))
    # repositories of geometric size, one language each, Zipf over languages
    repo_of = np.cumsum(rng.random(n_docs) < 1.0 / float(
        spec.get("repo_mean_docs", 1))) - 1
    repo_of -= repo_of[0]
    n_repos = int(repo_of[-1]) + 1
    p = 1.0 / np.arange(1, langs + 1) ** float(spec.get("lang_zipf", 1.0))
    repo_lang = rng.choice(langs, size=n_repos, p=p / p.sum())
    return lens, repo_lang[repo_of].astype(np.int64), repo_of


def token_corpus(spec: Dict[str, Any], seed: int, vocab: int) -> Corpus:
    lens, lang, repo_of = layout(spec)
    rng = np.random.default_rng(seed)
    # the seed reorders whole repositories, so label runs stay runs
    order_repos = rng.permutation(int(repo_of[-1]) + 1)
    rank = np.empty_like(order_repos)
    rank[order_repos] = np.arange(len(order_repos))
    order = np.argsort(rank[repo_of], kind="stable")
    lens, lang = lens[order], lang[order]
    offsets = np.concatenate([[0], np.cumsum(lens)]).astype(np.int64)
    tokens = zipf_ids(rng, int(offsets[-1]), vocab)
    return Corpus(tokens=tokens, offsets=offsets, lang=lang)
