"""Sharded scenario-axis sweeps: one stacked tensor, every local device.

The batched sweep engine prices a fleet's what-if grid in one array
pass — but that pass still lives on one device. Fleet-scale grids
(millions of scenarios; the ROADMAP north star) outgrow a single
accelerator long before they outgrow the DP itself, and the scenario
axis is embarrassingly parallel: scenario ``s``'s recurrence never
reads scenario ``t``. This module partitions exactly that axis:

* :func:`sharded_dp_tables` — the stacked ``C[S, N, L, L]`` tensor is
  padded to a multiple of the shard count, split over a 1-D device
  mesh with ``jax.shard_map``, and each shard runs the SAME vmapped ``lax.scan`` DP kernel the single-device
  JAX backend runs (:func:`repro.core.sweep._dp_jax_kernel` — shared
  by construction, so per-scenario arithmetic is identical and results
  are node-identical to ``backend="jax"``). Padding rows are replicas
  of the last real scenario and are dropped before anything reads
  them. ``kernel="pallas"`` swaps in the dense-mode Pallas tile kernel
  (:mod:`repro.core.pallas_dp`) per shard — bit-identical again, so
  the two compose for free.
* :func:`sharded_optimal_dp` — the :class:`~repro.core.sweep.
  BatchedSolverResult` wrapper: the full solver contract (per-scenario
  ``n_devices`` frozen-row subsetting, ``return_all_k``, the shared
  timing scope) over the sharded tables.

Entry points up the stack: ``batched_optimal_dp(backend="sharded")``,
``sweep(grid, backend="sharded")``, ``plan_split_batch(...,
backend="sharded")``, and ``build_surfaces(..., backend="sharded")``
all route here — a later multi-host mesh is a backend swap, not a
rewrite.

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
from repro.core.spec import MeshSpec

__all__ = [
    "mesh_from_spec",
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


# jax.distributed.initialize is once-per-process; flipped the first time
# a distributed MeshSpec resolves so repeat solves don't re-initialize.
_DISTRIBUTED_READY = False


def _ensure_distributed(mesh_spec: MeshSpec) -> None:
    """Bring up ``jax.distributed`` from a ``kind="distributed"`` spec.

    A spec with ``coordinator=None`` asserts the environment already
    initialized the runtime (e.g. a multi-host launcher did it before
    importing us); otherwise the spec's coordinator/process fields are
    the ``jax.distributed.initialize`` arguments. Idempotent."""
    global _DISTRIBUTED_READY
    if _DISTRIBUTED_READY:
        return
    if mesh_spec.coordinator is not None:
        import jax

        jax.distributed.initialize(
            coordinator_address=mesh_spec.coordinator,
            num_processes=mesh_spec.num_processes,
            process_id=mesh_spec.process_id,
        )
    _DISTRIBUTED_READY = True


def _resolve_shards(mesh_spec: MeshSpec | None, n_shards: int | None) -> int:
    """Shard count for a solve: explicit ``n_shards`` wins, then the
    spec's ``n_shards``, then every device the spec's mesh can see
    (local devices for ``kind="local"``/no spec, the GLOBAL device list
    for ``kind="distributed"``)."""
    if mesh_spec is None or mesh_spec.kind == "local":
        want = n_shards if n_shards is not None else (
            None if mesh_spec is None else mesh_spec.n_shards)
        return scenario_shards(want)
    _ensure_distributed(mesh_spec)
    import jax

    avail = len(jax.devices())
    want = n_shards if n_shards is not None else mesh_spec.n_shards
    if want is None:
        return avail
    if not 1 <= want <= avail:
        raise ValueError(
            f"n_shards={want} out of range [1, {avail}] "
            f"(global JAX devices: {avail})")
    return int(want)


def mesh_from_spec(mesh_spec: MeshSpec | None = None,
                   n_shards: int | None = None):
    """The 1-D scenario mesh a :class:`~repro.core.spec.MeshSpec`
    describes — THE multi-host seam.

    ``None`` or ``kind="local"`` builds exactly the historical mesh
    (the first ``n_shards`` LOCAL devices), so the single-host default
    is node-identical to the pre-spec sharded path by construction.
    ``kind="distributed"`` initializes ``jax.distributed`` from the
    spec (:func:`_ensure_distributed`) and spans the GLOBAL device
    list — scenario-axis partitioning already pads to any mesh, so
    multi-host is a device-list swap, not a new kernel."""
    import jax
    from jax.sharding import Mesh

    axis = "s" if mesh_spec is None else mesh_spec.axis
    if mesh_spec is None or mesh_spec.kind == "local":
        devices = jax.local_devices()
    else:
        _ensure_distributed(mesh_spec)
        devices = jax.devices()
    if n_shards is not None:
        devices = devices[:n_shards]
    return Mesh(np.array(devices), (axis,))


@functools.lru_cache(maxsize=None)
def _sharded_dp_solver(combine: str, n_shards: int, kernel: str = "jax",
                       block_s: int = 0, interpret: bool = False,
                       mesh_spec: MeshSpec | None = None):
    """Jitted ``shard_map`` wrapper over the shared DP kernel for one
    (combine, shard-count, kernel, mesh) tuple. Cached like the
    single-device solver (:func:`repro.core.sweep._dp_jax_solver`):
    repeat same-shape calls reuse the compiled executable, no retrace
    (:class:`~repro.core.spec.MeshSpec` is frozen/hashable, so it keys
    the cache like any other compile-relevant knob).

    ``kernel="jax"`` maps the vmapped ``lax.scan`` kernel;
    ``kernel="pallas"`` maps the dense-mode Pallas kernel
    (:func:`repro.core.pallas_dp._raw_pallas_fn` — each shard traces
    the exact single-device tile program, so sharded-pallas answers are
    node-identical to single-device pallas, which is node-identical to
    jax). ``block_s``/``interpret`` apply to the pallas kernel only."""
    import jax
    from jax import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    rep_kwargs = {}
    if kernel == "jax":
        fn = SW._dp_jax_kernel(combine)  # the SAME per-scenario math
    elif kernel == "pallas":
        from repro.core import pallas_dp as PD

        fn = PD._raw_pallas_fn("dense", combine, block_s, interpret)
        # pallas_call has no shard_map replication rule; the check is
        # moot anyway — every in/out spec partitions along "s"
        rep_kwargs = {"check_vma": False}
    else:
        raise ValueError(f"unknown shard kernel {kernel!r}; "
                         f"options: ['jax', 'pallas']")
    mesh = mesh_from_spec(mesh_spec, n_shards)
    axis = "s" if mesh_spec is None else mesh_spec.axis
    sharded = shard_map(
        fn, mesh=mesh,
        in_specs=(P(axis), P(axis)),
        out_specs=(P(axis), P(axis), P(axis)),
        **rep_kwargs,
    )
    # host operands go straight to their shards: without in_shardings a
    # host array lands whole on the first device before being split
    split = NamedSharding(mesh, P(axis))

    def solve_sharded(C, ns):  # XLA prints it as jit_solve_sharded
        return sharded(C, ns)

    return jax.jit(solve_sharded, in_shardings=(split, split))


def sharded_dp_tables(
    C: np.ndarray,
    combine: str = "sum",
    ns: np.ndarray | None = None,
    n_shards: int | None = None,
    kernel: str = "jax",
    block_s: int | None = None,
    interpret: bool | None = None,
    mesh_spec: MeshSpec | None = None,
):
    """(dp_per_k, parents) DP tables with the scenario axis sharded.

    The multi-device twin of :func:`repro.core.sweep._dp_jax` — same
    return contract, same frozen-row ``ns`` semantics, node-identical
    outputs (sharding partitions scenarios across devices; each
    scenario's float operation sequence is untouched). Scenario counts
    that do not divide the shard count are padded with replicas of the
    last scenario (an already-valid input row, so padding introduces no
    new inf/nan patterns) and the padding rows are sliced off before
    returning.

    ``kernel="pallas"`` runs the dense-mode Pallas tile kernel inside
    each shard instead of the ``lax.scan`` kernel (the two are
    bit-identical — :mod:`repro.core.pallas_dp`): inputs are +inf-padded
    to the lane tile in ``L`` and replica-padded so every shard holds a
    whole number of scenario blocks; ``block_s``/``interpret`` are the
    pallas knobs (``None`` = the pallas defaults).

    ``mesh_spec`` (a :class:`~repro.core.spec.MeshSpec`) names the
    device mesh: ``None``/local specs keep the historical local mesh
    (node-identical by construction — :func:`mesh_from_spec`);
    ``kind="distributed"`` spans the global multi-host device list."""
    Sn, N, L, _ = C.shape
    shards = _resolve_shards(mesh_spec, n_shards)
    ns_arr = np.full(Sn, N, dtype=np.int64) if ns is None \
        else np.asarray(ns, dtype=np.int64)
    import jax

    dtype = jax.dtypes.canonicalize_dtype(np.float64)
    if kernel == "pallas":
        from repro.core import pallas_dp as PD

        if N == 1 or Sn == 0:  # kernel-free cases: no scenario tiles
            return PD.pallas_dp_tables(C, combine, ns=ns_arr,
                                       block_s=block_s, interpret=interpret)
        bs, itp = PD._resolve_opts(block_s, interpret)
        Lp = PD._pad_lanes(L)
        Sp = Sn + _pad_to_multiple(Sn, shards * bs)  # whole blocks/shard
        solver = _sharded_dp_solver(combine, shards, "pallas", bs, itp,
                                    mesh_spec=mesh_spec)

        def operands():
            return (PD._pad_cost_tensor(C, Sp, Lp, dtype),
                    PD._pad_ns_column(ns_arr, Sn, Sp))
    else:
        Lp = L
        Sp = Sn + _pad_to_multiple(Sn, shards)
        solver = _sharded_dp_solver(combine, shards, kernel,
                                    mesh_spec=mesh_spec)
        ns_dtype = jax.dtypes.canonicalize_dtype(np.int64)

        def operands():
            Cp, nsp = C, ns_arr
            if Sp > Sn:  # replicas of the last scenario
                Cp = np.concatenate([C, np.repeat(C[-1:], Sp - Sn, axis=0)])
                nsp = np.concatenate([ns_arr, np.repeat(ns_arr[-1:], Sp - Sn)])
            # cast on the host: the bytes counted are the bytes that cross
            return np.asarray(Cp, dtype=dtype), nsp.astype(ns_dtype)

    dp0, dps, args = SW._dp_launch(
        "solve_sharded", solver, operands, rows=Sn, rows_padded=Sp,
        lanes=L, lanes_padded=Lp)
    return SW._dp_tables_to_numpy(dp0, dps, args, Sn, N, L)


def sharded_optimal_dp(
    C: np.ndarray,
    combine: str = "sum",
    return_all_k: bool = False,
    n_devices: np.ndarray | Sequence[int] | int | None = None,
    n_shards: int | None = None,
    kernel: str = "jax",
    mesh_spec: MeshSpec | None = None,
):
    """Exact split DP with the scenario axis sharded over local devices.

    The standalone entry point behind
    ``batched_optimal_dp(backend="sharded")`` — same arguments and
    return types as :func:`repro.core.sweep.batched_optimal_dp`, plus
    ``n_shards`` to pin the shard count (default: every local JAX
    device; see :func:`scenario_shards`) and ``kernel`` to pick the
    per-shard tile program (``"jax"`` or ``"pallas"`` — see
    :func:`sharded_dp_tables`; both are node-identical). Per-scenario
    ``n_devices`` and ``return_all_k`` carry the full solver contract;
    results are node-identical to the single-device JAX backend and
    cost-close to the NumPy float64 oracle (bit-identical under an x64
    JAX config)."""
    Sn, N, L, ns = SW._validate_dp_inputs(C, return_all_k, n_devices)
    with span("dp"):
        t0 = time.perf_counter()
        dp_per_k, parents = sharded_dp_tables(C, combine, ns=ns,
                                              n_shards=n_shards,
                                              kernel=kernel,
                                              mesh_spec=mesh_spec)
        return SW._results_from_dp_tables(dp_per_k, parents, L, N, Sn,
                                          "sharded", ns, return_all_k, t0)
