"""Pallas fused cost-construction + DP kernel (``backend="pallas"``).

The batched JAX backend (:func:`repro.core.sweep._dp_jax`) consumes a
fully materialized ``C[S, N, L, L]`` cost tensor: every scenario's
per-device segment-cost matrix is built on the host, shipped to the
accelerator, and round-tripped through HBM before the recurrence reads
each entry exactly once. At fleet scale the tensor build rivals the
solve itself (BENCH_sweep.json) and the ``S`` axis — the one axis
related work multiplies (per-device channels, heterogeneous platforms)
— pays for bandwidth, not math.

This module moves the construction INSIDE the kernel. The cost tensor
decomposes exactly as the sweep engine already assembles it::

    C[s, k, a, b] = local[k, a, b] + tx[s, b]

where ``local`` is the link-independent per-device local-cost stack
(``(N, L, L)``, from the ``(DeviceProfile, is_first)`` bank) and ``tx``
is the per-scenario transmission vector (``(S, L)``). A Pallas kernel
tiles the scenario axis over a 1-D grid; each grid step holds one
``(block_s, L)`` DP row tile plus the shared ``local`` stack in
VMEM and fuses ``local + tx`` into the ``min``/``argmin`` reduction of
device step ``k`` — the 4-D ``C`` tensor never exists, on host or
device. Per-scenario VMEM footprint is ``O(N * L^2)`` for the shared
stack plus ``O(block_s * L)`` rows, not ``O(S * N * L^2)``.

Two kernel modes share one body:

* **dense** — consumes a prebuilt ``C`` (the :func:`repro.core.sweep.
  batched_optimal_dp` seam takes a tensor, so ``backend="pallas"``
  must too). Arithmetic is ordered exactly like the JAX backend's
  ``vmap``/``lax.scan`` kernel, so dense-mode tables and parents are
  bit-identical to ``backend="jax"`` — the property-test contract.
* **fused** — consumes ``(local, tx)`` (or a ``(bank, bank_idx, tx)``
  triple for heterogeneous device mixes) and never materializes ``C``.
  The only arithmetic difference from the jax backend is construction
  rounding: fused computes ``f32(local) + f32(tx)`` where the dense
  path computes ``f32(local64 + tx64)`` — a <=1 ulp cost wobble. Plan
  nodes are therefore identical EXCEPT under exact-cost ties, where
  the wobble may break the tie toward a different equally-optimal
  plan (zero float64-repriced regret — the same class of divergence
  the float32 jax backend already shows against the float64 oracle;
  ``benchmarks/sweep_grid.py --backend pallas`` verifies every
  divergent node is such a tie). Costs are always allclose.

Tiling: ``L`` is +inf-padded to the 128-lane float32 tile and ``S`` is
replica-padded to a ``block_s`` multiple (default 8, the float32
sublane tile). Padding is semantically invisible — +inf candidates
never win a first-minimum ``argmin``, replica rows are sliced off
before anything reads them.

CPU/CI: Pallas lowers to Mosaic on TPU; elsewhere the ``interpret=``
escape hatch (default ON off-TPU, see :func:`pallas_interpret_default`)
runs the same kernel through the Pallas interpreter — identical
numerics and tie-breaks, no speedup. The CI ``pallas`` job asserts
correctness in interpret mode; the >=10x fusion win is a real-hardware
claim.

Entry points up the stack: ``batched_optimal_dp(backend="pallas")``
(dense), and ``sweep(grid, backend="pallas")`` and
``build_surfaces(..., backend="pallas")`` (fused, via
:func:`pallas_fused_optimal_dp`). On the bank path without all-k (the
one ``sweep`` takes) the fused kernel's program also selects each
scenario's cost and walks its split points (``solve_fused_walk``), so
only those leave the device; every other path fetches the tables.

Precision follows the active JAX config like every JAX-side backend:
float32 by default, float64 when ``jax.config.jax_enable_x64`` is on.
"""

from __future__ import annotations

import functools
import time
from typing import Sequence

import numpy as np

from repro.core import sweep as SW
from repro.core.spans import span

__all__ = [
    "LANE",
    "DEFAULT_BLOCK_S",
    "pallas_interpret_default",
    "pallas_dp_tables",
    "pallas_fused_dp_tables",
    "pallas_optimal_dp",
    "pallas_fused_optimal_dp",
]

INF = float("inf")

# float32 TPU tile: 8 sublanes x 128 lanes. L pads to the lane multiple,
# the scenario grid steps in sublane-multiple blocks.
LANE = 128
DEFAULT_BLOCK_S = 8

# Incremented every time the pallas solver is (re)traced; a same-shape
# repeat call must leave it unchanged (jit-cache regression test in
# tests/test_pallas_dp.py — same pattern as sweep._DP_JAX_TRACE_COUNT).
_PALLAS_TRACE_COUNT = 0


def pallas_interpret_default() -> bool:
    """Whether ``interpret=None`` means interpret mode: True off-TPU.

    On TPU the kernel compiles through Mosaic; everywhere else (CPU CI,
    GPU hosts without a Triton lowering for this kernel) the Pallas
    interpreter runs the same tile program with identical numerics."""
    import jax

    return jax.default_backend() != "tpu"


def _pad_lanes(L: int) -> int:
    """L padded up to the 128-lane tile multiple (min one full lane)."""
    return max(LANE, -(-L // LANE) * LANE)


def _pad_rows(S: int, block_s: int) -> int:
    """S padded up to a whole number of scenario blocks."""
    return -(-S // block_s) * block_s


def _dp_step_tile(dp, ck_shift, ns, k, combine):
    """One fused device step on a scenario tile — the Pallas twin of the
    ``lax.scan`` body in :func:`repro.core.sweep._dp_jax_kernel`.

    ``dp`` is the ``(T, L)`` running table, ``ck_shift[t, a, b]`` the
    segment cost of layers ``[a+2, b+1]`` on device ``k`` (already
    boundary-shifted so candidate ``a`` aligns with parent ``a + 1``),
    ``ns`` the ``(T, 1)`` per-scenario fleet sizes. Candidate order,
    first-minimum ``argmin`` and the frozen-row mask mirror the jax
    kernel exactly — +inf-padded lanes never win, scenarios whose fleet
    completed at ``n_s < k`` carry their stale table forward."""
    import jax.numpy as jnp

    if combine == "sum":
        cand = dp[:, :, None] + ck_shift
    else:
        cand = jnp.maximum(dp[:, :, None], ck_shift)
    ndp = jnp.min(cand, axis=1)
    arg = jnp.where(jnp.isfinite(ndp), SW._first_argmin(cand, ndp, 1) + 1, -1)
    act = ns >= k
    ndp = jnp.where(act, ndp, dp)
    arg = jnp.where(act, arg, -1)
    return ndp, arg


def _dense_kernel(N: int, Lp: int, combine: str):
    """Kernel body for a prebuilt per-tile cost tensor ``C``."""
    import jax.numpy as jnp

    def kernel(C_ref, ns_ref, dp0_ref, dps_ref, args_ref):
        ns = ns_ref[...]            # (T, 1) int32
        dp = C_ref[:, 0, 0, :]      # (T, Lp): device-1 row, a == 0
        dp0_ref[...] = dp
        for k in range(2, N + 1):   # unrolled: N is small and static
            ck = C_ref[:, k - 1]    # (T, Lp, Lp)
            ck_shift = jnp.concatenate(
                [ck[:, 1:], jnp.full((ck.shape[0], 1, Lp), INF, ck.dtype)],
                axis=1)
            dp, arg = _dp_step_tile(dp, ck_shift, ns, k, combine)
            dps_ref[:, k - 2, :] = dp
            args_ref[:, k - 2, :] = arg

    return kernel


def _fused_kernel(N: int, Lp: int, combine: str):
    """Kernel body fusing ``C = local + tx`` into the recurrence.

    ``local`` (the shared ``(N, Lp, Lp)`` per-device stack) and ``tx``
    (the ``(T, Lp)`` per-tile transmission rows) are the ONLY inputs —
    each device step materializes one boundary-shifted ``(T, Lp, Lp)``
    candidate slab in VMEM registers and reduces it immediately; the
    full ``C[S, N, L, L]`` tensor never exists."""
    import jax.numpy as jnp

    def kernel(local_ref, tx_ref, ns_ref, dp0_ref, dps_ref, args_ref):
        tx = tx_ref[...]            # (T, Lp)
        ns = ns_ref[...]            # (T, 1) int32
        # device-1 row fused on the fly: C[s, 0, 0, b] = local[0,0,b]+tx[s,b]
        dp = local_ref[0, 0, :][None, :] + tx
        dp0_ref[...] = dp
        for k in range(2, N + 1):
            ck = local_ref[k - 1]   # (Lp, Lp), shared across the tile
            ck_shift = jnp.concatenate(
                [ck[1:], jnp.full((1, Lp), INF, ck.dtype)], axis=0)
            ckf = ck_shift[None, :, :] + tx[:, None, :]
            dp, arg = _dp_step_tile(dp, ckf, ns, k, combine)
            dps_ref[:, k - 2, :] = dp
            args_ref[:, k - 2, :] = arg

    return kernel


def _raw_pallas_fn(mode: str, combine: str, block_s: int, interpret: bool):
    """The traceable (unjitted) pallas_call wrapper for one kernel mode.

    Shape-polymorphic: the ``pallas_call`` (grid, block specs, output
    shapes) is constructed at trace time from the operand shapes, so one
    wrapper serves every (S, N, L) — jit re-specializes per shape like
    every other backend. Callers pass pre-padded operands: ``Lp`` a lane
    multiple (+inf padding), ``Sp`` a ``block_s`` multiple (replica
    rows), ``ns`` as an ``(Sp, 1)`` int32 column."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    if mode == "dense":

        def fn(Cp, nsp):
            Sp, N, Lp, _ = Cp.shape
            return pl.pallas_call(
                _dense_kernel(N, Lp, combine),
                grid=(Sp // block_s,),
                in_specs=[
                    pl.BlockSpec((block_s, N, Lp, Lp),
                                 lambda i: (i, 0, 0, 0)),
                    pl.BlockSpec((block_s, 1), lambda i: (i, 0)),
                ],
                out_specs=[
                    pl.BlockSpec((block_s, Lp), lambda i: (i, 0)),
                    pl.BlockSpec((block_s, N - 1, Lp), lambda i: (i, 0, 0)),
                    pl.BlockSpec((block_s, N - 1, Lp), lambda i: (i, 0, 0)),
                ],
                out_shape=[
                    jax.ShapeDtypeStruct((Sp, Lp), Cp.dtype),
                    jax.ShapeDtypeStruct((Sp, N - 1, Lp), Cp.dtype),
                    jax.ShapeDtypeStruct((Sp, N - 1, Lp), jnp.int32),
                ],
                interpret=interpret,
                name="solve_dense",
            )(Cp, nsp)

        return fn

    if mode == "fused":

        def fn(localp, txp, nsp):
            N, Lp, _ = localp.shape
            Sp = txp.shape[0]
            return pl.pallas_call(
                _fused_kernel(N, Lp, combine),
                grid=(Sp // block_s,),
                in_specs=[
                    # the local stack rides along whole: same block every
                    # grid step (index map pins it), so it loads once
                    pl.BlockSpec((N, Lp, Lp), lambda i: (0, 0, 0)),
                    pl.BlockSpec((block_s, Lp), lambda i: (i, 0)),
                    pl.BlockSpec((block_s, 1), lambda i: (i, 0)),
                ],
                out_specs=[
                    pl.BlockSpec((block_s, Lp), lambda i: (i, 0)),
                    pl.BlockSpec((block_s, N - 1, Lp), lambda i: (i, 0, 0)),
                    pl.BlockSpec((block_s, N - 1, Lp), lambda i: (i, 0, 0)),
                ],
                out_shape=[
                    jax.ShapeDtypeStruct((Sp, Lp), localp.dtype),
                    jax.ShapeDtypeStruct((Sp, N - 1, Lp), localp.dtype),
                    jax.ShapeDtypeStruct((Sp, N - 1, Lp), jnp.int32),
                ],
                interpret=interpret,
                name="solve_fused",
            )(localp, txp, nsp)

        return fn

    raise ValueError(f"unknown pallas kernel mode {mode!r}")


@functools.lru_cache(maxsize=None)
def _pallas_dp_solver(mode: str, combine: str, block_s: int,
                      interpret: bool):
    """Jitted entry to :func:`_raw_pallas_fn`, cached per configuration,
    named ``solve_<mode>`` (XLA prints ``jit_solve_fused`` /
    ``jit_solve_dense``).

    ``jax.jit``'s executable cache keys on operand shapes, so two
    same-shape calls compile exactly once (regression-tested via
    :data:`_PALLAS_TRACE_COUNT`, the :data:`repro.core.sweep.
    _DP_JAX_TRACE_COUNT` pattern)."""
    import jax

    fn = _raw_pallas_fn(mode, combine, block_s, interpret)

    def solve(*operands):
        global _PALLAS_TRACE_COUNT
        _PALLAS_TRACE_COUNT += 1  # Python side effect: runs at trace only
        return fn(*operands)

    solve.__name__ = solve.__qualname__ = f"solve_{mode}"
    return jax.jit(solve)


def _pick(x, idx):
    """``x[s, idx[s]]`` of an ``(S, W)`` array: a minimum over each row
    with every other entry masked to the dtype's largest value, which
    returns the picked entry bit for bit and vectorises on the chip as a
    gather does not."""
    import jax.numpy as jnp
    from jax import lax

    hit = lax.broadcasted_iota(jnp.int32, x.shape, 1) == idx[:, None]
    fill = jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) \
        else jnp.iinfo(x.dtype).max
    return jnp.min(jnp.where(hit, x, fill), axis=1)


def _walk_tables(dp0, dps, args, ns, L: int):
    """Each scenario's cost and split points from the fused kernel's
    padded tables, on the device: the jnp twin of
    :func:`repro.core.sweep._results_from_dp_tables` with per-scenario
    fleet sizes ``ns`` ``(Sp,)``.

    The cost is table ``ns[s]``'s entry at boundary ``L`` (``dp0`` for
    one device), in the kernel dtype. The walk follows
    :func:`repro.core.sweep._reconstruct_splits`' rules over the same
    ``args``: from boundary ``L`` down through ``k = N..2``, the parent
    at ``clip(b - 1, 0, L - 1)``, -1 where the cost is not finite,
    written only where ``ns[s] >= k`` (else -1), and ``b`` moved only on
    those rows. Returns ``cost`` ``(Sp,)`` and ``splits`` ``(Sp, N-1)``
    int32."""
    import jax.numpy as jnp

    N = dps.shape[1] + 1
    final = jnp.concatenate([dp0[:, None, L - 1], dps[:, :, L - 1]], axis=1)
    cost = _pick(final, ns - 1)
    feas = jnp.isfinite(cost)
    b = jnp.full_like(ns, L)
    cols = []
    for k in range(N, 1, -1):  # unrolled: N is small and static
        a = jnp.where(feas, _pick(args[:, k - 2], jnp.clip(b - 1, 0, L - 1)),
                      -1)
        act = ns >= k
        cols.append(jnp.where(act, a, -1))
        b = jnp.where(act, jnp.clip(jnp.where(feas, a, 1), 1, L), b)
    return cost, jnp.stack(cols[::-1], axis=1)


@functools.lru_cache(maxsize=None)
def _pallas_walk_solver(combine: str, block_s: int, interpret: bool, L: int):
    """Jitted fused kernel followed by :func:`_walk_tables`, cached per
    configuration and unpadded layer count ``L``, named
    ``solve_fused_walk`` (the trace prints ``jit_solve_fused_walk``).

    One program: the tables stay on the device, and a launch returns
    each scenario's cost and splits, ``Sp * 4 * N`` bytes where the
    tables are ``Sp * 4 * (2N - 1) * Lp``."""
    import jax

    fn = _raw_pallas_fn("fused", combine, block_s, interpret)

    def solve_fused_walk(localp, txp, nsp):
        global _PALLAS_TRACE_COUNT
        _PALLAS_TRACE_COUNT += 1  # Python side effect: runs at trace only
        return _walk_tables(*fn(localp, txp, nsp), nsp[:, 0], L)

    return jax.jit(solve_fused_walk)


def _resolve_opts(block_s: int | None, interpret: bool | None):
    bs = DEFAULT_BLOCK_S if block_s is None else int(block_s)
    if bs < 1:
        raise ValueError(f"block_s must be >= 1, got {block_s}")
    itp = pallas_interpret_default() if interpret is None else bool(interpret)
    return bs, itp


def _pad_ns_column(ns_arr: np.ndarray, Sn: int, Sp: int) -> np.ndarray:
    nsp = np.zeros((Sp, 1), dtype=np.int32)
    nsp[:Sn, 0] = ns_arr
    if Sp > Sn:
        nsp[Sn:, 0] = ns_arr[-1]  # replica rows keep a valid fleet size
    return nsp


def _pad_cost_tensor(C: np.ndarray, Sp: int, Lp: int, dtype) -> np.ndarray:
    """``C`` cast to the kernel dtype, +inf-padded to ``Lp`` lanes and
    replica-padded to ``Sp`` rows. Built directly in ``dtype``: a float64
    staging copy would double the host footprint of the padded tensor
    (10.7 GB at S=16,384, N=5, Lp=128), and the cast rounds each entry
    exactly as the device transfer of a float64 copy would."""
    Sn, N, L, _ = C.shape
    Cp = np.full((Sp, N, Lp, Lp), INF, dtype=dtype)
    Cp[:Sn, :, :L, :L] = C
    if Sp > Sn:
        Cp[Sn:] = Cp[Sn - 1]  # replica rows: already-valid inputs
    return Cp


def _trivial_tables(dp0, Sn: int, N: int, L: int, dtype):
    """Host-side tables for the kernel-free cases (N == 1 or S == 0)."""
    dps = np.zeros((Sn, max(N - 1, 0), L), dtype=dtype)
    args = np.full((Sn, max(N - 1, 0), L), -1, dtype=np.int32)
    return SW._dp_tables_to_numpy(dp0, dps, args, Sn, N, L)


def pallas_dp_tables(
    C: np.ndarray,
    combine: str = "sum",
    ns: np.ndarray | None = None,
    *,
    block_s: int | None = None,
    interpret: bool | None = None,
):
    """(dp_per_k, parents) DP tables from the dense-mode Pallas kernel.

    The pallas twin of :func:`repro.core.sweep._dp_jax` — same return
    contract, same frozen-row ``ns`` semantics, and bit-identical
    tables AND parents (dense mode reorders no arithmetic; it only
    tiles the scenario axis). ``L`` is +inf-padded to the 128-lane
    tile, ``S`` replica-padded to a ``block_s`` multiple; padding is
    sliced off before returning. ``interpret=None`` resolves via
    :func:`pallas_interpret_default`."""
    Sn, N, L, _ = C.shape
    ns_arr = np.full(Sn, N, dtype=np.int64) if ns is None \
        else np.asarray(ns, dtype=np.int64)
    import jax

    dtype = jax.dtypes.canonicalize_dtype(np.float64)
    if N == 1 or Sn == 0:
        # no recurrence to run: device-1 row IS the answer (cast like the
        # jit boundary would), and an empty scenario axis has no tiles
        return _trivial_tables(C[:, 0, 0, :].astype(dtype), Sn, N, L, dtype)
    bs, itp = _resolve_opts(block_s, interpret)
    Lp, Sp = _pad_lanes(L), _pad_rows(Sn, bs)
    import jax.numpy as jnp

    def operands():
        return (jnp.asarray(_pad_cost_tensor(C, Sp, Lp, dtype)),
                jnp.asarray(_pad_ns_column(ns_arr, Sn, Sp)))

    dp0, dps, args = SW._dp_launch(
        "solve_dense", _pallas_dp_solver("dense", combine, bs, itp),
        operands, rows=Sn, rows_padded=Sp, lanes=L, lanes_padded=Lp)
    return SW._dp_tables_to_numpy(dp0, dps, args, Sn, N, L)


def _empty_tables(Sn: int, N: int, L: int, dtype):
    """Host (dp0, dps, args) for the fused launches to scatter into."""
    return (np.empty((Sn, L), dtype=dtype),
            np.empty((Sn, N - 1, L), dtype=dtype),
            np.empty((Sn, N - 1, L), dtype=np.int32))


def _fused_launch(local, tx, ns_arr, sel, kernel, solver, bs, dtype, into):
    """One fused kernel launch over the scenarios ``sel`` of ``tx`` /
    ``ns_arr`` on the shared stack ``local``, through the jitted
    ``solver`` named ``kernel``: ``solve_fused`` scatters its unpadded
    tables into rows ``sel`` of ``into`` (:func:`_empty_tables`),
    ``solve_fused_walk`` its costs and splits. N >= 2, at least one
    scenario."""
    N, L, _ = local.shape
    import jax.numpy as jnp

    ns_sel = ns_arr[sel]
    Sn = len(ns_sel)
    Lp, Sp = _pad_lanes(L), _pad_rows(Sn, bs)

    def operands():
        localp = np.full((N, Lp, Lp), INF, dtype=np.float64)
        localp[:, :L, :L] = local
        txp = np.zeros((Sp, Lp), dtype=np.float64)
        txp[:Sn, :L] = tx[sel]
        if Sp > Sn:
            txp[Sn:] = txp[Sn - 1]
        return (jnp.asarray(localp, dtype=dtype), jnp.asarray(txp, dtype=dtype),
                jnp.asarray(_pad_ns_column(ns_sel, Sn, Sp)))

    SW._dp_launch(kernel, solver, operands, rows=Sn, rows_padded=Sp,
                  lanes=L, lanes_padded=Lp, into=(into, sel))


def _fused_dp0_host(local, tx, dtype):
    """The N == 1 fused answer, cast exactly like the jit boundary."""
    return local[0, 0, :].astype(dtype)[None, :] + tx.astype(dtype)


def _join_live_stacks(bank_idx: np.ndarray, ns_arr: np.ndarray):
    """The device stacks the fused bank path launches on, one launch each.

    Device slots at or beyond a scenario's own fleet size ``n_s`` are
    dead: the kernel masks every step ``k > n_s`` and carries the row's
    stale table forward, so a scenario can ride any stack whose first
    ``n_s`` slots are its live slots. The group's distinct (live slots,
    ``n_s``) pairs are taken longest first, in ``np.unique`` order within
    a length; a pair joins the first stack already chosen that extends
    its live slots, else its dead slots copy its last live slot and that
    stack is chosen. A homogeneous mix thus takes one stack whatever its
    fleet sizes, and so does a heterogeneous ``(d1, d2, d3)`` over fleet
    sizes 1-3. The loop runs over pairs, never over scenarios.

    Returns ``(stacks, launch_of, n_canon)``: the ``(U, N)`` stacks, the
    ``(S,)`` stack of each scenario, and how many distinct stacks the
    rows have with dead slots read as bank row 0 (the launches one per
    stack would make without the join)."""
    N = bank_idx.shape[1]
    canon = np.where(np.arange(N)[None, :] >= ns_arr[:, None], 0, bank_idx)
    pairs, inv = np.unique(np.column_stack([canon, ns_arr]), axis=0,
                           return_inverse=True)
    n_canon = len(np.unique(pairs[:, :N], axis=0))
    stacks: list[np.ndarray] = []
    stack_of = np.empty(len(pairs), dtype=np.int64)
    for p in np.argsort(-pairs[:, N], kind="stable"):
        n = int(pairs[p, N])
        live = pairs[p, :n]
        for u, stack in enumerate(stacks):
            if np.array_equal(stack[:n], live):
                stack_of[p] = u
                break
        else:
            stack_of[p] = len(stacks)
            stacks.append(np.concatenate([live, np.full(N - n, live[-1])]))
    return np.stack(stacks), stack_of[inv.reshape(-1)], n_canon


def pallas_fused_dp_tables(
    local: np.ndarray,
    tx: np.ndarray,
    combine: str = "sum",
    ns: np.ndarray | None = None,
    *,
    block_s: int | None = None,
    interpret: bool | None = None,
):
    """(dp_per_k, parents) DP tables WITHOUT ever materializing ``C``.

    ``local`` is the shared per-device local-cost stack ``(N, L, L)``
    (``SplitCostModel.local_cost_tensor``), ``tx`` the per-scenario
    transmission vectors ``(S, L)``; the kernel fuses
    ``C[s,k] = local[k] + tx[s]`` into each reduction step. Plan nodes
    (parents) match the dense path exactly except under exact-cost
    ties; dp costs may differ by construction rounding (<=1 ulp per
    entry — see the module docstring). Scenario ``s`` never reads the
    slots ``local[k]``, ``k >= ns[s]``. Heterogeneous device mixes go
    through :func:`pallas_fused_optimal_dp`, which joins scenarios into
    device stacks and launches the same kernel once a stack."""
    local = np.asarray(local, dtype=np.float64)
    tx = np.asarray(tx, dtype=np.float64)
    if local.ndim != 3 or local.shape[1] != local.shape[2]:
        raise ValueError(f"local must be (N, L, L), got {local.shape}")
    N, L, _ = local.shape
    if tx.ndim != 2 or tx.shape[1] != L:
        raise ValueError(f"tx must be (S, {L}), got {tx.shape}")
    Sn = tx.shape[0]
    ns_arr = np.full(Sn, N, dtype=np.int64) if ns is None \
        else np.asarray(ns, dtype=np.int64)
    import jax

    dtype = jax.dtypes.canonicalize_dtype(np.float64)
    if N == 1 or Sn == 0:
        return _trivial_tables(_fused_dp0_host(local, tx, dtype),
                               Sn, N, L, dtype)
    bs, itp = _resolve_opts(block_s, interpret)
    tables = _empty_tables(Sn, N, L, dtype)
    _fused_launch(local, tx, ns_arr, slice(None), "solve_fused",
                  _pallas_dp_solver("fused", combine, bs, itp), bs, dtype,
                  tables)
    return SW._dp_tables_to_numpy(*tables, Sn, N, L)


def pallas_optimal_dp(
    C: np.ndarray,
    combine: str = "sum",
    return_all_k: bool = False,
    n_devices: np.ndarray | Sequence[int] | int | None = None,
    *,
    block_s: int | None = None,
    interpret: bool | None = None,
):
    """Exact split DP on the dense-mode Pallas kernel.

    The standalone entry behind ``batched_optimal_dp(backend="pallas")``
    — same arguments and return types, plus the pallas knobs
    (``block_s`` scenario tile, ``interpret`` escape hatch). Carries the
    full solver contract (per-scenario ``n_devices`` frozen rows,
    ``return_all_k``, the shared timing scope) and is node-identical to
    ``backend="jax"``: bit-equal tables, bit-equal parents."""
    Sn, N, L, ns = SW._validate_dp_inputs(C, return_all_k, n_devices)
    with span("dp"):
        t0 = time.perf_counter()
        dp_per_k, parents = pallas_dp_tables(C, combine, ns=ns,
                                             block_s=block_s,
                                             interpret=interpret)
        return SW._results_from_dp_tables(dp_per_k, parents, L, N, Sn,
                                          "pallas", ns, return_all_k, t0)


def pallas_fused_optimal_dp(
    bank: np.ndarray,
    bank_idx: np.ndarray | None,
    tx: np.ndarray,
    combine: str = "sum",
    return_all_k: bool = False,
    n_devices: np.ndarray | Sequence[int] | int | None = None,
    *,
    block_s: int | None = None,
    interpret: bool | None = None,
):
    """Exact split DP from compact profiles — ``C`` is never built.

    The fused entry behind ``sweep(grid, backend="pallas")`` and
    ``build_surfaces(..., backend="pallas")``:

    Args:
      bank: ``(B, L, L)`` local-cost bank (one matrix per distinct
        ``(DeviceProfile, is_first)`` pair, the sweep engine's profile
        bank) — or, when ``bank_idx is None``, the shared per-device
        ``(N, L, L)`` local stack itself (the homogeneous / surface
        case).
      bank_idx: ``(S, N)`` integer rows into ``bank`` (scenario ``s``'s
        device ``k`` uses ``bank[bank_idx[s, k]]``), or ``None``.
      tx: ``(S, L)`` per-scenario transmission vectors.
      combine / return_all_k / n_devices: the
        :func:`repro.core.sweep.batched_optimal_dp` solver contract.

    Scenarios are subgrouped by device stack: device slots at or beyond
    a scenario's own ``n_devices`` are dead (the kernel never reads
    them), so a scenario joins any stack that extends its live slots
    (:func:`_join_live_stacks`) and one mix takes one stack over all its
    fleet sizes. Each subgroup runs one fused kernel pass. Without
    ``return_all_k`` the same program picks each scenario's cost and
    walks its splits on the device (:func:`_walk_tables`), and only those
    scatter back into grid order; with it, the tables do. The bank is
    small by construction — distinct live stacks, not scenarios, bound
    the subgroup count. The ``repro.dp`` span counts ``stacks``
    (distinct stacks with dead slots read as row 0), ``launches`` and,
    on the walked path, ``walked`` (the scenarios).

    Bottleneck variants need NO kernel change: a variant reprices only
    the cut (compressed airtime + encoder time), both functions of the
    boundary layer ``b`` alone, so the sweep engine folds them into the
    per-scenario ``tx`` rows and the ``local + tx[s, b]`` decomposition
    above — and hence this kernel — holds verbatim. Joint
    (split, variant) solves fold the variant axis into the scenario
    axis upstream (:func:`repro.core.sweep.solve_variant_bank`); this
    entry only ever sees a flat scenario batch."""
    bank = np.asarray(bank, dtype=np.float64)
    tx = np.asarray(tx, dtype=np.float64)
    if tx.ndim != 2:
        raise ValueError(f"tx must be (S, L), got {tx.shape}")
    Sn, L = tx.shape
    if bank.ndim != 3 or bank.shape[1:] != (L, L):
        raise ValueError(f"bank must be (B, {L}, {L}), got {bank.shape}")

    if bank_idx is None:
        N = bank.shape[0]
        if return_all_k and n_devices is not None:
            raise ValueError("return_all_k and per-scenario n_devices "
                             "are mutually exclusive")
        ns = None if n_devices is None else SW._normalize_ns(n_devices, Sn, N)
        with span("dp"):
            t0 = time.perf_counter()
            dp_per_k, parents = pallas_fused_dp_tables(
                bank, tx, combine, ns=ns, block_s=block_s,
                interpret=interpret)
            return SW._results_from_dp_tables(dp_per_k, parents, L, N, Sn,
                                              "pallas", ns, return_all_k, t0)

    bank_idx = np.asarray(bank_idx, dtype=np.int64)
    if bank_idx.ndim != 2 or bank_idx.shape[0] != Sn:
        raise ValueError(
            f"bank_idx must be ({Sn}, N), got {bank_idx.shape}")
    N = bank_idx.shape[1]
    if return_all_k and n_devices is not None:
        raise ValueError("return_all_k and per-scenario n_devices "
                         "are mutually exclusive")
    ns = None if n_devices is None else SW._normalize_ns(n_devices, Sn, N)
    import jax

    dtype = jax.dtypes.canonicalize_dtype(np.float64)
    with span("dp") as sp:
        t0 = time.perf_counter()
        ns_arr = np.full(Sn, N, dtype=np.int64) if ns is None else ns
        if Sn == 0 or N == 1:
            dp0 = np.empty((Sn, L), dtype=dtype)
            for s in range(Sn):
                dp0[s] = _fused_dp0_host(bank[bank_idx[s]], tx[s:s + 1],
                                         dtype)[0]
            dp_per_k, parents = _trivial_tables(dp0, Sn, N, L, dtype)
            return SW._results_from_dp_tables(dp_per_k, parents, L, N, Sn,
                                              "pallas", ns, return_all_k, t0)
        bs, itp = _resolve_opts(block_s, interpret)
        stacks, launch_of, n_stacks = _join_live_stacks(bank_idx, ns_arr)
        sp.set_metadata(stacks=n_stacks, launches=len(stacks))
        if return_all_k:  # the caller reads every table: fetch them
            kernel, solver = "solve_fused", _pallas_dp_solver(
                "fused", combine, bs, itp)
            host = _empty_tables(Sn, N, L, dtype)
        else:
            sp.set_metadata(walked=Sn)
            kernel, solver = "solve_fused_walk", _pallas_walk_solver(
                combine, bs, itp, L)
            host = (np.empty(Sn, dtype=dtype),
                    np.empty((Sn, N - 1), dtype=np.int32))
        for u in range(len(stacks)):
            _fused_launch(bank[stacks[u]], tx, ns_arr,
                          np.flatnonzero(launch_of == u),
                          kernel, solver, bs, dtype, host)
        if return_all_k:
            dp_per_k, parents = SW._dp_tables_to_numpy(*host, Sn, N, L)
            return SW._results_from_dp_tables(dp_per_k, parents, L, N, Sn,
                                              "pallas", ns, True, t0)
        with span("dp.reconstruct"):
            cost = host[0].astype(np.float64)
            splits = host[1].astype(np.int64)
            feasible = np.isfinite(cost)
            return SW.BatchedSolverResult(
                solver="batched_dp", backend="pallas", n_devices=N,
                splits=splits, cost_s=cost, feasible=feasible,
                wall_time_s=time.perf_counter() - t0, n_devices_s=ns)
