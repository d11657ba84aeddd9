"""Plain reference of the semantics the benchmark checks, written from the
Spinner paper (arXiv:1404.3861), importing nothing of the system under test.

* ``RefGraph``: the weighted undirected graph of Eq. 3 -- every pair {u, v}
  joined by a directed input edge gets weight 2 when both directions were
  given, else 1 (an undirected input gives 1).  Self-loops and duplicates
  count once or not at all.
* ``loads``, ``phi``, ``rho``: Eq. 6 loads (weighted degree per label), the
  share of local edges and the largest load over the ideal one (Eq. 13).
* ``lpa``: Spinner's label propagation (Sections 3.1-3.3) in plain
  ``jax.numpy``: dense (V, k) neighbour-label sums, the Eq. 7-8 penalised
  score with a random tie-break and a bonus for the current label, the Eq.
  11-12 migration probability with candidates weighted by degree, and the
  Section 3.3 halting rule.  ``balance=False`` drops the load penalty and
  the migration throttle: plain label propagation, which breaks the
  configuration's balance guarantee (the control).
"""
from __future__ import annotations

import numpy as np


class RefGraph:
    """Symmetric adjacency (each pair both ways) with Eq. 3 weights."""

    def __init__(self, n: int, src, dst, directed: bool):
        s = np.asarray(src, np.int64)
        d = np.asarray(dst, np.int64)
        keep = s != d
        s, d = s[keep], d[keep]
        if directed:
            arcs = np.unique(s * n + d)           # each direction once
            s, d = arcs // n, arcs % n
        pair, dirs = np.unique(np.minimum(s, d) * n + np.maximum(s, d),
                               return_counts=True)
        w = dirs.astype(np.float64) if directed else np.ones(pair.size)
        lo, hi = pair // n, pair % n
        self.n = n
        self.src = np.concatenate([lo, hi]).astype(np.int32)
        self.dst = np.concatenate([hi, lo]).astype(np.int32)
        self.w = np.concatenate([w, w])
        self.deg = np.bincount(self.src, weights=self.w, minlength=n)

    @property
    def total_weight(self) -> float:
        return float(self.deg.sum())


def loads(deg: np.ndarray, labels: np.ndarray, k: int) -> np.ndarray:
    """Eq. 6: weighted degree per label (float64, exact for integers)."""
    return np.bincount(labels, weights=deg, minlength=k)[:k]


def phi(g: RefGraph, labels: np.ndarray) -> float:
    """Share of edges whose two ends carry one label."""
    return float(np.mean(labels[g.src] == labels[g.dst]))


def rho(deg: np.ndarray, labels: np.ndarray, k: int) -> float:
    """Eq. 13: the largest load over the ideal load."""
    return float(loads(deg, labels, k).max() / (deg.sum() / k))


def lpa(g: RefGraph, cfg: dict, init: np.ndarray, seed: int,
        balance: bool = True, block: int = 1 << 24):
    """Spinner from ``init`` to its halting iteration, on the default
    device.  Returns ``(labels, loads, iterations, halted)`` on the host.

    Each iteration's neighbour-label sums are accumulated over blocks of
    ``block`` edges, so that the reference fits beside nothing else.
    """
    import jax
    import jax.numpy as jnp

    k, n = int(cfg["k"]), g.n
    cap = float(cfg["c"]) * g.total_weight / k               # Eq. 5
    eps, window = np.float32(cfg["eps"]), int(cfg["halt_window"])
    tie, bonus = float(cfg["tie_noise"]), float(cfg["current_bonus"])
    nb = -(-g.src.size // block)
    pad = nb * block - g.src.size
    src = jnp.asarray(np.pad(g.src, (0, pad)).reshape(nb, block))
    dst = jnp.asarray(np.pad(g.dst, (0, pad)).reshape(nb, block))
    w = jnp.asarray(np.pad(g.w.astype(np.float32), (0, pad))
                    .reshape(nb, block))
    degj = jnp.asarray(g.deg, jnp.float32)
    by_degree = cfg["migration_weighting"] == "edges"

    def neighbour_sums(labels):
        def add(acc, blk):
            s, d, ww = blk
            return acc.at[s, labels[d]].add(ww), None
        acc, _ = jax.lax.scan(add, jnp.zeros((n, k), jnp.float32),
                              (src, dst, w))
        return acc

    def iteration(carry):
        labels, ld, key, best, stall, it, _ = carry
        key, k_tie, k_mig = jax.random.split(key, 3)
        score = neighbour_sums(labels) / jnp.maximum(degj, 1.0)[:, None]
        if balance:
            score = score - (ld / cap)[None, :]                # Eq. 7-8
        pick = (score + tie * jax.random.uniform(k_tie, (n, k))
                + bonus * jax.nn.one_hot(labels, k))
        want_l = jnp.argmax(pick, axis=1).astype(jnp.int32)
        want = want_l != labels
        if balance:                                           # Eq. 11-12
            mass = degj if by_degree else jnp.ones_like(degj)
            m = jnp.zeros(k).at[want_l].add(jnp.where(want, mass, 0.0))
            p = jnp.clip(jnp.maximum(cap - ld, 0.0)
                         / jnp.maximum(m, 1e-9), 0.0, 1.0)
            want = want & (jax.random.uniform(k_mig, (n,)) < p[want_l])
        new = jnp.where(want, want_l, labels)
        new_ld = jnp.zeros(k).at[new].add(degj)
        # Eq. 9: score(G), each vertex scored at its new label
        s_g = jnp.sum(jnp.take_along_axis(score, new[:, None], 1))
        improved = s_g > best + eps * jnp.maximum(1.0, jnp.abs(best))
        stall = jnp.where(improved, jnp.int32(0), stall + 1)
        return (new, new_ld, key, jnp.maximum(best, s_g), stall, it + 1,
                stall >= window)

    def going(carry):
        return (~carry[6]) & (carry[5] < int(cfg["max_iters"]))

    labels0 = jnp.asarray(init, jnp.int32)
    carry = (labels0, jnp.zeros(k).at[labels0].add(degj),
             jax.random.PRNGKey(seed), jnp.float32(-jnp.inf), jnp.int32(0),
             jnp.int32(0), jnp.asarray(False))
    labels, ld, _, _, _, it, halted = jax.jit(
        lambda c: jax.lax.while_loop(going, iteration, c))(carry)
    return (np.asarray(labels), np.asarray(ld, np.float64), int(it),
            bool(halted))
