"""The comparisons that decide ``correct``.

- ``stream_errors``: the token blocks that reached the train step against
  the corpus's own documents under the cell's filter.  Each epoch's blocks,
  row after row, must be whole kept documents back to back, each at most
  once, the last possibly cut short; targets must be the tokens shifted by
  one.  Returns the number of blocks holding a token that is not so.
- ``norm_gap``: the worst leaf's gap between two per-leaf norms, taken
  against the reference's norm of that leaf or of the median leaf,
  whichever is larger; ``rel_to_leaf`` takes any per-leaf gap so, such as
  the norm of the difference of two gradients.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np

from .gen.corpus import Corpus

KEY = 16        # tokens that index a document; no document is shorter


def _rows(tokens: np.ndarray, targets: np.ndarray) -> Optional[np.ndarray]:
    """The block's stream (B*(S+1) tokens), or None if targets do not
    follow the tokens."""
    if not np.array_equal(targets[:, :-1], tokens[:, 1:]):
        return None
    return np.concatenate([tokens, targets[:, -1:]], axis=1).reshape(-1)


def stream_errors(blocks: Sequence[Dict[str, np.ndarray]], corpus: Corpus,
                  keep: np.ndarray, epoch_starts: Sequence[int] = (0,)) -> int:
    kept = np.flatnonzero(keep)
    index: Dict[bytes, List[int]] = {}
    for d in kept:
        index.setdefault(corpus.doc(d)[:KEY].tobytes(), []).append(int(d))
    bounds = list(epoch_starts) + [len(blocks)]
    bad = 0
    for e0, e1 in zip(bounds[:-1], bounds[1:]):
        streams, owner = [], []
        for b in range(e0, e1):
            s = _rows(blocks[b]["tokens"], blocks[b]["targets"])
            if s is None:
                bad += 1
                s = np.full(blocks[b]["tokens"].size + len(
                    blocks[b]["tokens"]), -1, np.int32)
            streams.append(s)
            owner.append(np.full(len(s), b))
        if not streams:
            continue
        stream, owner = np.concatenate(streams), np.concatenate(owner)
        used: set = set()
        pos = 0
        while pos < len(stream):
            rest = stream[pos:]
            cands = (index.get(rest[:KEY].tobytes(), []) if len(rest) >= KEY
                     else [int(d) for d in kept])
            hit = None
            for d in cands:
                doc = corpus.doc(d)
                n = min(len(doc), len(rest))
                if d not in used and np.array_equal(rest[:n], doc[:n]):
                    hit = (d, len(doc))
                    break
            if hit is None:
                # nothing can be verified from here to the epoch's end
                bad += len(set(owner[pos:].tolist()))
                break
            used.add(hit[0])
            pos += hit[1]
    return bad


def norm_gap(prog: Dict[str, float], ref: Dict[str, float],
             leaves: Optional[Sequence[str]] = None) -> float:
    names = list(leaves) if leaves is not None else sorted(ref)
    return rel_to_leaf({k: abs(prog[k] - ref[k]) for k in names}, ref)


def rel_to_leaf(gap: Dict[str, float], ref: Dict[str, float]) -> float:
    """The worst of per-leaf gaps, each over the reference's norm of that
    leaf or of the median leaf, whichever is larger."""
    med = float(np.median([ref[k] for k in ref]))
    return max((g / max(ref[k], med) for k, g in gap.items()), default=0.0)


def moved_leaves(ref_grad: Dict[str, float], floor: float = 1e-3
                 ) -> List[str]:
    """Leaves whose reference gradient is not nought to rounding: at least
    ``floor`` times the median leaf's.  Others (a key's bias, under softmax)
    move under Adam by round-off alone."""
    med = float(np.median(list(ref_grad.values())))
    return sorted(k for k, v in ref_grad.items() if v >= floor * med)


def loss_gap(prog: Sequence[float], ref: Sequence[float]) -> float:
    return max(abs(p - r) / abs(r) for p, r in zip(prog, ref))
