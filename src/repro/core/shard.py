"""Sharded scenario-axis sweeps: one stacked tensor, every local device.

The batched sweep engine prices a fleet's what-if grid in one array
pass — but that pass still lives on one device. Fleet-scale grids
(millions of scenarios; the ROADMAP north star) outgrow a single
accelerator long before they outgrow the DP itself, and the scenario
axis is embarrassingly parallel: scenario ``s``'s recurrence never
reads scenario ``t``. This module partitions exactly that axis:

* :func:`sharded_dp_tables` — the stacked ``C[S, N, L, L]`` tensor is
  padded to a multiple of the shard count, split over a 1-D mesh of
  the local devices with ``jax.shard_map``, and each shard runs the
  SAME vmapped ``lax.scan`` DP kernel the single-device JAX backend
  runs (:func:`repro.core.sweep._dp_jax_kernel` — shared by
  construction, so per-scenario arithmetic is identical and results
  are node-identical to ``backend="jax"``). Padding rows are replicas
  of the last real scenario and are dropped before anything reads
  them.
* :func:`sharded_optimal_dp` — the :class:`~repro.core.sweep.
  BatchedSolverResult` wrapper: the full solver contract (per-scenario
  ``n_devices`` frozen-row subsetting, ``return_all_k``, the shared
  timing scope) over the sharded tables.

Entry points up the stack: ``batched_optimal_dp(backend="sharded")``,
``sweep(grid, backend="sharded")``, ``plan_split_batch(...,
backend="sharded")``, and ``build_surfaces(..., backend="sharded")``
all route here.

CPU testing: ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
(set BEFORE jax imports) splits the host into 8 XLA devices; the CI
``multi-device`` job and ``tests/test_shard.py`` subprocess tests run
exactly that. With one visible device the sharded path degenerates to
the single-device JAX backend plus a no-op mesh — always safe to call.

Precision follows the active JAX config like the single-device
backend: float32 by default (equal-cost tie-breaks may differ from the
float64 oracle), float64 — with scalar-oracle tie-break parity — when
``jax.config.jax_enable_x64`` is on.

Bottleneck-variant banks ride the same partition: a joint
(split, variant) solve folds the variant axis into the scenario axis
(:func:`repro.core.sweep.solve_variant_bank` reshapes ``(V, S, N, L,
L)`` to ``(V*S, N, L, L)`` variant-major) BEFORE dispatch, so the
shards see an ordinary — just ``V×`` taller — scenario batch and the
per-scenario independence that justifies the mesh is untouched.
"""

from __future__ import annotations

import functools
import time
from typing import Sequence

import numpy as np

from repro.core import sweep as SW
from repro.core.spans import span

__all__ = [
    "scenario_shards",
    "sharded_dp_tables",
    "sharded_optimal_dp",
]


def scenario_shards(n_shards: int | None = None) -> int:
    """The shard count a sharded solve will use.

    ``None`` means every local JAX device (1 on a plain CPU host;
    ``--xla_force_host_platform_device_count=D`` makes it ``D``). An
    explicit ``n_shards`` must not exceed the local device count —
    fewer is allowed (e.g. benchmarking weak scaling on a wide host)."""
    import jax

    avail = jax.local_device_count()
    if n_shards is None:
        return avail
    if not 1 <= n_shards <= avail:
        raise ValueError(
            f"n_shards={n_shards} out of range [1, {avail}] "
            f"(local JAX devices: {avail})")
    return int(n_shards)


def _pad_to_multiple(S: int, n_shards: int) -> int:
    """Rows to append so ``S + pad`` divides evenly into ``n_shards``
    equal shards (0 when it already does) — arbitrary scenario counts
    ride a fixed mesh by replica-padding, never by dropping work."""
    return (-S) % n_shards


@functools.lru_cache(maxsize=None)
def _sharded_dp_solver(combine: str, n_shards: int):
    """Jitted ``shard_map`` wrapper that maps the vmapped ``lax.scan``
    DP kernel over the first ``n_shards`` local devices, for one
    (combine, shard-count) pair. Cached like the single-device solver
    (:func:`repro.core.sweep._dp_jax_solver`): repeat same-shape calls
    reuse the compiled executable, no retrace."""
    import jax
    from jax import shard_map
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.local_devices()[:n_shards]), ("s",))
    sharded = shard_map(
        SW._dp_jax_kernel(combine),  # the SAME per-scenario math
        mesh=mesh,
        in_specs=(P("s"), P("s")),
        out_specs=(P("s"), P("s"), P("s")),
    )
    # host operands go straight to their shards: without in_shardings a
    # host array lands whole on the first device before being split
    split = NamedSharding(mesh, P("s"))

    def solve_sharded(C, ns):  # XLA prints it as jit_solve_sharded
        return sharded(C, ns)

    return jax.jit(solve_sharded, in_shardings=(split, split))


def sharded_dp_tables(
    C: np.ndarray,
    combine: str = "sum",
    ns: np.ndarray | None = None,
    n_shards: int | None = None,
):
    """(dp_per_k, parents) DP tables with the scenario axis sharded.

    The multi-device twin of :func:`repro.core.sweep._dp_jax` — same
    return contract, same frozen-row ``ns`` semantics, node-identical
    outputs (sharding partitions scenarios across devices; each
    scenario's float operation sequence is untouched). Scenario counts
    that do not divide the shard count are padded with replicas of the
    last scenario (an already-valid input row, so padding introduces no
    new inf/nan patterns) and the padding rows are sliced off before
    returning. ``n_shards`` pins the shard count (see
    :func:`scenario_shards`)."""
    Sn, N, L, _ = C.shape
    shards = scenario_shards(n_shards)
    ns_arr = np.full(Sn, N, dtype=np.int64) if ns is None \
        else np.asarray(ns, dtype=np.int64)
    import jax

    dtype = jax.dtypes.canonicalize_dtype(np.float64)
    ns_dtype = jax.dtypes.canonicalize_dtype(np.int64)
    Sp = Sn + _pad_to_multiple(Sn, shards)
    solver = _sharded_dp_solver(combine, shards)

    def operands():
        Cp, nsp = C, ns_arr
        if Sp > Sn:  # replicas of the last scenario
            Cp = np.concatenate([C, np.repeat(C[-1:], Sp - Sn, axis=0)])
            nsp = np.concatenate([ns_arr, np.repeat(ns_arr[-1:], Sp - Sn)])
        # cast on the host: the bytes counted are the bytes that cross
        return np.asarray(Cp, dtype=dtype), nsp.astype(ns_dtype)

    dp0, dps, args = SW._dp_launch(
        "solve_sharded", solver, operands, rows=Sn, rows_padded=Sp,
        lanes=L, lanes_padded=L)
    return SW._dp_tables_to_numpy(dp0, dps, args, Sn, N, L)


def sharded_optimal_dp(
    C: np.ndarray,
    combine: str = "sum",
    return_all_k: bool = False,
    n_devices: np.ndarray | Sequence[int] | int | None = None,
    n_shards: int | None = None,
):
    """Exact split DP with the scenario axis sharded over local devices.

    The standalone entry point behind
    ``batched_optimal_dp(backend="sharded")`` — same arguments and
    return types as :func:`repro.core.sweep.batched_optimal_dp`, plus
    ``n_shards`` to pin the shard count (default: every local JAX
    device; see :func:`scenario_shards`). Per-scenario
    ``n_devices`` and ``return_all_k`` carry the full solver contract;
    results are node-identical to the single-device JAX backend and
    cost-close to the NumPy float64 oracle (bit-identical under an x64
    JAX config)."""
    Sn, N, L, ns = SW._validate_dp_inputs(C, return_all_k, n_devices)
    with span("dp"):
        t0 = time.perf_counter()
        dp_per_k, parents = sharded_dp_tables(C, combine, ns=ns,
                                              n_shards=n_shards)
        return SW._results_from_dp_tables(dp_per_k, parents, L, N, Sn,
                                          "sharded", ns, return_all_k, t0)
