"""Heavy-hitter recovery fidelity of the chunked-cyclic sketch at FetchSGD
scale, vs an ideal 2-universal hash-based count-sketch.

Geometry: d ~ 6.5M (ResNet9 grad size), 5 rows x 500k cols, k = 50k — the
FetchSGD headline CIFAR10 config (reference utils.py:142-162, csvec usage at
fed_aggregator.py:584-611). Input vectors are power-law (Zipf-magnitude,
random sign, random coordinate placement) — the shape of momentum-accumulated
gradients FetchSGD relies on.

Measures, per trial and family:
  - top-k mass recall: |union(est_topk, true_topk) mass| / true top-k mass
  - relative L2 error of the recovered k-sparse update vs the true top-k
    vector
  - relative L2 error of the estimated values on the true top-k support

Run on CPU:
  JAX_PLATFORMS=cpu python scripts/sketch_fidelity.py

Results are recorded in docs/sketch_fidelity.md.
"""

from __future__ import annotations

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

D = 6_568_640          # ResNet9 CIFAR10 grad size ballpark
R, C, K = 5, 500_000, 50_000
ALPHA = 1.1            # Zipf exponent
TRIALS = 3


def powerlaw_vector(rng: np.random.RandomState, d: int) -> np.ndarray:
    mags = (np.arange(1, d + 1, dtype=np.float64)) ** (-ALPHA)
    signs = rng.choice([-1.0, 1.0], size=d)
    v = mags * signs
    rng.shuffle(v)
    return v.astype(np.float32)


def ideal_count_sketch(rng, v, r, c, k):
    """2-universal-ish (full random) hash count-sketch in numpy."""
    d = v.size
    est_rows = np.empty((r, d), np.float32)
    for j in range(r):
        buckets = rng.randint(0, c, size=d)
        signs = rng.choice([-1.0, 1.0], size=d).astype(np.float32)
        table = np.zeros(c, np.float32)
        np.add.at(table, buckets, v * signs)
        est_rows[j] = table[buckets] * signs
    est = np.median(est_rows, axis=0)
    idx = np.argpartition(np.abs(est), d - k)[d - k:]
    out = np.zeros(d, np.float32)
    out[idx] = est[idx]
    return out


def chunked_cyclic(v, r, c, k, seed):
    import jax.numpy as jnp

    from commefficient_tpu.ops.sketch import make_sketch, sketch_vec, unsketch

    cs = make_sketch(v.size, c=c, r=r, seed=seed, num_blocks=20)
    table = sketch_vec(cs, jnp.asarray(v))
    return np.asarray(unsketch(cs, table, k))


def metrics(v, recovered, k):
    d = v.size
    true_idx = np.argpartition(np.abs(v), d - k)[d - k:]
    true_topk = np.zeros(d, np.float32)
    true_topk[true_idx] = v[true_idx]
    true_mass = float(np.sum(v[true_idx] ** 2))

    rec_idx = np.flatnonzero(recovered)
    common = np.intersect1d(true_idx, rec_idx, assume_unique=False)
    recall_mass = float(np.sum(v[common] ** 2)) / true_mass

    rel_l2_update = float(np.linalg.norm(recovered - true_topk)
                          / np.linalg.norm(true_topk))
    rel_l2_vals = float(np.linalg.norm(recovered[common] - v[common])
                        / np.linalg.norm(v[common])) if common.size else np.nan
    return recall_mass, rel_l2_update, rel_l2_vals


def main():
    rows = []
    for trial in range(TRIALS):
        rng = np.random.RandomState(100 + trial)
        v = powerlaw_vector(rng, D)

        t0 = time.time()
        rec_cc = chunked_cyclic(v, R, C, K, seed=200 + trial)
        t_cc = time.time() - t0
        m_cc = metrics(v, rec_cc, K)

        t0 = time.time()
        rec_id = ideal_count_sketch(rng, v, R, C, K)
        t_id = time.time() - t0
        m_id = metrics(v, rec_id, K)

        rows.append(("chunked-cyclic", trial) + m_cc + (t_cc,))
        rows.append(("ideal-hash", trial) + m_id + (t_id,))
        print(f"trial {trial}: cc recall={m_cc[0]:.4f} relL2={m_cc[1]:.4f} "
              f"vals={m_cc[2]:.4f} ({t_cc:.1f}s) | ideal recall={m_id[0]:.4f} "
              f"relL2={m_id[1]:.4f} vals={m_id[2]:.4f} ({t_id:.1f}s)",
              flush=True)

    print("\nfamily            recall_mass  rel_l2_update  rel_l2_vals")
    for fam in ("chunked-cyclic", "ideal-hash"):
        sel = [r for r in rows if r[0] == fam]
        rm = np.mean([r[2] for r in sel])
        ru = np.mean([r[3] for r in sel])
        rv = np.mean([r[4] for r in sel])
        print(f"{fam:<18} {rm:10.4f} {ru:13.4f} {rv:12.4f}")


if __name__ == "__main__":
    main()
