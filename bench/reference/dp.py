"""Plain reference of the exact split DP.

For each scenario with fleet size n, over split points
0 = b_0 < b_1 < ... < b_n = L:

  dp_1[b] = C_1[1, b]
  dp_k[b] = min over a < b of  dp_{k-1}[a] + C_k[a+1, b]
  answer  = dp_n[L]

The same code runs in float64 with NumPy (the reference) and, with
``xp=jax.numpy`` and a lower ``dtype``, as the control: the reference
put in the program's place one precision down.
"""

from __future__ import annotations

import numpy as np

INF = float("inf")


def tables(first_row, seg, ns_max: int, xp=np):
    """DP tables for a block of R scenarios.

    ``first_row`` is (R, L): the cost of layers 1..b on device 1.
    ``seg(k)`` gives device k's (R, L, L) or (L, L) segment costs,
    [a-1, b-1] for layers a..b (k >= 2), in the working dtype.
    Returns (dp, parents): dp (R, ns_max, L), parents (R, ns_max, L)
    with parents[:, k-1, b] = the last layer index (0-based end) of the
    device-(k-1) segment, -1 for k = 1 or no finite candidate."""
    R, L = first_row.shape
    dps = [first_row]
    parents = [xp.full((R, L), -1, dtype=xp.int32)]
    dp = first_row
    for k in range(2, ns_max + 1):
        c = seg(k)
        # candidate: previous table ends at layer a+1 (index a); this
        # segment covers layers a+2..b+1, i.e. c[a+1, b]
        shifted = c[..., 1:, :]
        pad = xp.full(shifted.shape[:-2] + (1, L), INF, dtype=shifted.dtype)
        shifted = xp.concatenate([shifted, pad], axis=-2)
        cand = dp[:, :, None] + shifted
        ndp = xp.min(cand, axis=1)
        arg = xp.argmin(cand, axis=1).astype(xp.int32)
        arg = xp.where(xp.isfinite(ndp), arg, -1)
        dps.append(ndp)
        parents.append(arg)
        dp = ndp
    return xp.stack(dps, axis=1), xp.stack(parents, axis=1)


def splits_from(parents: np.ndarray, ns: np.ndarray, L: int) -> np.ndarray:
    """(R, max(ns)-1) split points (1-based layer after which each cut
    falls), -1 padded, walking parents back from dp_n[L]."""
    R = parents.shape[0]
    n_max = int(ns.max()) if R else 1
    out = np.full((R, max(n_max - 1, 0)), -1, dtype=np.int64)
    for r in range(R):
        n = int(ns[r])
        b = L - 1
        for k in range(n, 1, -1):
            a = int(parents[r, k - 1, b])
            if a < 0:
                out[r, :] = -1
                break
            out[r, k - 2] = a + 1
            b = a
    return out
