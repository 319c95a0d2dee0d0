"""Planner tier: one serializable form of a planning request.

The planning entry points (``solve_batched`` / ``solve_multi_channel`` /
``solve_variant_bank``, ``plan_split_batch``, ``build_surfaces``) take
keyword arguments. A request that lives in kwargs cannot be serialized,
and a request that cannot be serialized cannot cross a process boundary
— which a process-pool surface rebuild must. This module is the
declarative form of those same requests:

* :class:`PlanSpec` — a frozen, declarative description of ONE planning
  request: what to solve (scenario tensor shape / embedded surface
  problem), how (solver + backend + combine), and under which
  constraints (fleet-size vector, channel weights, energy budget,
  variant bank, accuracy floor). ``to_json``/``from_json`` round-trip
  every field exactly — finite floats bit-exact via ``repr``, non-finite
  floats through an explicit ``{"__float__": ...}`` tag so the payload
  is strict, NaN-free JSON — and the spec pickles, so it crosses both
  ``json`` and ``multiprocessing`` boundaries.

* :class:`PlannerService` — resolves a spec (plus its big operand — a
  stacked cost tensor, a list of cost models) by checking the operand
  against the spec and calling the matching public entry point, so a
  spec solve and the equivalent kwargs call are the same code and
  bit-identical (property-tested in ``tests/test_spec.py`` across all
  four ``DP_BACKENDS``).

* :func:`build_surfaces_from_spec` — the module-level (hence picklable)
  worker a :class:`~repro.core.async_replan.SurfaceRebuilder` submits
  to a ``ProcessPoolExecutor``: the spec ships to the worker process,
  the surfaces ship back, and the generation/swap semantics in the
  parent are untouched.

Import discipline: this module sits ABOVE the engines it resolves to
(:mod:`repro.core.sweep`, :mod:`repro.core.planner`,
:mod:`repro.core.surface`); none of them imports it.
"""

from __future__ import annotations

import dataclasses
import json
import math
from dataclasses import dataclass
from typing import Mapping, Sequence

import numpy as np

from repro.core import planner as PL
from repro.core import surface as SF
from repro.core import sweep as SW
from repro.core.latency import (
    COST_CHANNELS,
    BottleneckVariant,
    ContentionModel,
    DeviceProfile,
    LayerCost,
    LinkProfile,
    ModelCostProfile,
    SplitCostModel,
)

__all__ = [
    "PlanSpec",
    "PlannerService",
    "ScenarioRef",
    "SurfaceAxes",
    "build_surfaces_from_spec",
    "solve_from_json",
]


@dataclass(frozen=True)
class ScenarioRef:
    """What a spec's scenario axis refers to.

    ``kind`` names the operand family the service expects alongside the
    spec: ``"tensor"`` (a stacked ``(S, N, L, L)`` cost tensor),
    ``"channels"`` (``(ch, S, N, L, L)``), ``"variant_bank"``
    (``(V, S, N, L, L)``), ``"models"`` (a list of cost models), or
    ``"surface"`` (no operand — the problem is embedded in the spec's
    ``cost_model``/``protocols``/``surface`` fields). ``shape`` pins the
    operand shape for validation at resolve time."""

    kind: str
    shape: tuple[int, ...] | None = None
    count: int | None = None

    _KINDS = ("tensor", "channels", "variant_bank", "models", "surface")

    def __post_init__(self):
        if self.kind not in self._KINDS:
            raise ValueError(f"unknown scenario kind {self.kind!r}; "
                             f"options: {list(self._KINDS)}")


@dataclass(frozen=True)
class SurfaceAxes:
    """The (packet-time × loss) grid axes of a surface-building spec.

    ``loss_p`` keeps the :func:`~repro.core.surface.build_surfaces`
    convention: ``None`` entries resolve to each protocol's base loss;
    a ``None`` axis means base loss only. ``chunk_candidates`` are the
    explicit activation-chunk candidates (``None`` = per-protocol
    defaults)."""

    pt_scale: tuple[float, ...]
    loss_p: tuple[float | None, ...] | None
    chunk_candidates: tuple[int, ...] | None = None


@dataclass(frozen=True)
class PlanSpec:
    """One declarative, serializable planning request.

    Every field is a frozen primitive / tuple / registered frozen
    dataclass, so the spec round-trips exactly through
    :meth:`to_json`/:meth:`from_json` AND through ``pickle`` — the
    contract that lets a request cross a process boundary. Construct
    directly, or via the builders (:func:`tensor_spec`,
    :func:`channels_spec`, :func:`variant_bank_spec`,
    :func:`models_spec`, :func:`surfaces_spec`), which take the
    matching entry point's keyword arguments.

    ``n_devices`` is the fleet-size vector: ``None`` (tensor width),
    one ``int`` for every scenario, or a per-scenario tuple.
    ``solver_options`` carries solver-specific kwargs (``beam_width``,
    ``return_all_k``, ...) as sorted ``(key, value)`` pairs so the spec
    stays hashable-by-field and order-insensitive."""

    solver: str = "batched_dp"
    backend: str = "numpy"
    combine: str = "sum"
    scenario: ScenarioRef | None = None
    n_devices: int | tuple[int, ...] | None = None
    channels: tuple[str, ...] | None = None
    channel_weights: tuple[float, ...] | None = None
    channel_combines: tuple[str, ...] | None = None
    energy_budget: float | tuple[float, ...] | None = None
    variants: tuple[BottleneckVariant, ...] | None = None
    accuracy_proxy: tuple[float, ...] | None = None
    accuracy_floor: float | None = None
    cost_model: SplitCostModel | None = None
    protocols: tuple[tuple[str, LinkProfile], ...] | None = None
    surface: SurfaceAxes | None = None
    solver_options: tuple[tuple[str, object], ...] = ()

    def options(self) -> dict:
        """``solver_options`` as a plain kwargs dict."""
        return dict(self.solver_options)

    def to_json(self) -> str:
        """Strict (NaN-free) JSON encoding; exact field round-trip via
        :meth:`from_json`. Finite floats survive bit-for-bit (``repr``
        round-trip); non-finite floats are tagged
        ``{"__float__": "inf"|"-inf"|"nan"}`` so ``allow_nan=False``
        always holds."""
        return json.dumps(_encode(self), sort_keys=True, allow_nan=False)

    @classmethod
    def from_json(cls, payload: str) -> "PlanSpec":
        obj = _decode(json.loads(payload, parse_constant=_reject_constant))
        if not isinstance(obj, cls):
            raise ValueError(
                f"payload decodes to {type(obj).__name__}, not PlanSpec")
        return obj


# ---------------------------------------------------------------------------
# JSON codec (tagged, recursive, NaN-free)
# ---------------------------------------------------------------------------

# every dataclass a PlanSpec may embed, by name. Decoding instantiates
# ONLY these types — an unknown __type__ tag is an error, not an eval.
_SPEC_TYPES: dict[str, type] = {
    cls.__name__: cls
    for cls in (
        LayerCost,
        DeviceProfile,
        LinkProfile,
        ContentionModel,
        BottleneckVariant,
        ModelCostProfile,
        SplitCostModel,
        ScenarioRef,
        SurfaceAxes,
        PlanSpec,
    )
}


def _reject_constant(token: str):
    raise ValueError(f"non-strict JSON constant {token!r} in PlanSpec "
                     f"payload (the codec tags non-finite floats)")


def _encode(obj):
    if obj is None or isinstance(obj, (bool, str)):
        return obj
    if isinstance(obj, (int, np.integer)):
        return int(obj)
    if isinstance(obj, (float, np.floating)):
        f = float(obj)
        if math.isfinite(f):
            return f
        tag = "nan" if math.isnan(f) else ("inf" if f > 0 else "-inf")
        return {"__float__": tag}
    if isinstance(obj, tuple):
        return {"__tuple__": [_encode(v) for v in obj]}
    if isinstance(obj, list):
        return [_encode(v) for v in obj]
    name = type(obj).__name__
    if dataclasses.is_dataclass(obj) and _SPEC_TYPES.get(name) is type(obj):
        out: dict = {"__type__": name}
        for f in dataclasses.fields(obj):
            out[f.name] = _encode(getattr(obj, f.name))
        return out
    raise TypeError(f"PlanSpec JSON codec cannot encode "
                    f"{type(obj).__name__}: {obj!r}")


_FLOAT_TAGS = {"nan": float("nan"), "inf": float("inf"),
               "-inf": float("-inf")}


def _decode(obj):
    if isinstance(obj, dict):
        if set(obj) == {"__float__"}:
            return _FLOAT_TAGS[obj["__float__"]]
        if set(obj) == {"__tuple__"}:
            return tuple(_decode(v) for v in obj["__tuple__"])
        if "__type__" in obj:
            try:
                cls = _SPEC_TYPES[obj["__type__"]]
            except KeyError:
                raise ValueError(f"unknown PlanSpec type tag "
                                 f"{obj['__type__']!r}") from None
            return cls(**{k: _decode(v) for k, v in obj.items()
                          if k != "__type__"})
        return {k: _decode(v) for k, v in obj.items()}
    if isinstance(obj, list):
        return [_decode(v) for v in obj]
    return obj


# ---------------------------------------------------------------------------
# Normalization: kwargs values -> frozen spec fields, value-preserving
# ---------------------------------------------------------------------------


def _norm_n(n) -> int | tuple[int, ...] | None:
    """Fleet sizes -> None / int / tuple[int, ...]. Value-preserving:
    the solver re-derives the exact same ``np.int64`` vector from the
    tuple, so spec-path results stay bit-identical."""
    if n is None or isinstance(n, (int, np.integer)):
        return None if n is None else int(n)
    return tuple(int(v) for v in np.asarray(n).reshape(-1))


def _norm_budget(b) -> float | tuple[float, ...] | None:
    if b is None:
        return None
    arr = np.asarray(b, dtype=np.float64)
    if arr.ndim == 0:
        return float(arr)
    return tuple(float(v) for v in arr)


def _norm_floats(seq) -> tuple[float, ...] | None:
    if seq is None:
        return None
    return tuple(float(v) for v in np.asarray(seq, dtype=np.float64))


def _norm_loss(loss_p) -> tuple[float | None, ...] | None:
    if loss_p is None:
        return None
    return tuple(None if lp is None else float(lp) for lp in loss_p)


def _norm_options(options: Mapping[str, object]) -> tuple:
    return tuple(sorted(options.items()))


def _norm_variants(variants) -> tuple[BottleneckVariant, ...] | None:
    return None if variants is None else tuple(variants)


# ---------------------------------------------------------------------------
# Spec builders — one per entry point, taking its keyword arguments
# ---------------------------------------------------------------------------


def tensor_spec(C, *, solver="batched_dp", combine="sum", backend="numpy",
                n_devices=None, **options) -> PlanSpec:
    """Spec for a plain batched solve over a stacked ``(S, N, L, L)``
    tensor (the :func:`repro.core.sweep.solve_batched` contract)."""
    return PlanSpec(
        solver=solver, backend=backend, combine=combine,
        scenario=ScenarioRef(kind="tensor",
                             shape=tuple(int(d) for d in np.shape(C))),
        n_devices=_norm_n(n_devices),
        solver_options=_norm_options(options),
    )


def channels_spec(C, *, channels=COST_CHANNELS, solver="batched_dp",
                  combine="sum", backend="numpy", n_devices=None,
                  energy_budget=None, channel_weights=None,
                  channel_combines=None, **options) -> PlanSpec:
    """Spec for a multi-channel solve over ``(ch, S, N, L, L)`` (the
    :func:`repro.core.sweep.solve_multi_channel` contract)."""
    return PlanSpec(
        solver=solver, backend=backend, combine=combine,
        scenario=ScenarioRef(kind="channels",
                             shape=tuple(int(d) for d in np.shape(C))),
        n_devices=_norm_n(n_devices),
        channels=tuple(channels),
        channel_weights=_norm_floats(channel_weights),
        channel_combines=(None if channel_combines is None
                          else tuple(channel_combines)),
        energy_budget=_norm_budget(energy_budget),
        solver_options=_norm_options(options),
    )


def variant_bank_spec(C, *, solver="batched_dp", combine="sum",
                      backend="numpy", n_devices=None, accuracy_proxy=None,
                      accuracy_floor=None, **options) -> PlanSpec:
    """Spec for a joint (split, variant) solve over ``(V, S, N, L, L)``
    (the :func:`repro.core.sweep.solve_variant_bank` contract)."""
    return PlanSpec(
        solver=solver, backend=backend, combine=combine,
        scenario=ScenarioRef(kind="variant_bank",
                             shape=tuple(int(d) for d in np.shape(C))),
        n_devices=_norm_n(n_devices),
        accuracy_proxy=_norm_floats(accuracy_proxy),
        accuracy_floor=(None if accuracy_floor is None
                        else float(accuracy_floor)),
        solver_options=_norm_options(options),
    )


def models_spec(cost_models, *, n_devices, solver="batched_dp",
                backend="numpy", energy_budget=None, variants=None,
                accuracy_floor=None, **options) -> PlanSpec:
    """Spec for a cost-model batch (the
    :func:`repro.core.planner.plan_split_batch` contract). The models
    travel ALONGSIDE the spec (they are the big operand); the spec
    records the request shape."""
    combine = "sum"
    if cost_models and cost_models[0].objective == "bottleneck":
        combine = "max"
    return PlanSpec(
        solver=solver, backend=backend, combine=combine,
        scenario=ScenarioRef(kind="models", count=len(cost_models)),
        n_devices=_norm_n(n_devices),
        energy_budget=_norm_budget(energy_budget),
        variants=_norm_variants(variants),
        accuracy_floor=(None if accuracy_floor is None
                        else float(accuracy_floor)),
        solver_options=_norm_options(options),
    )


def surfaces_spec(cost_model, protocols, sizes, *, pt_scale, loss_p,
                  solver="batched_beam", backend="numpy", beam_width=8,
                  chunk_candidates=None, energy_budget=None, variants=None,
                  accuracy_floor=None) -> PlanSpec:
    """Spec for a surface-family build (the
    :func:`repro.core.surface.build_surfaces` contract). Unlike the
    tensor specs this one is SELF-CONTAINED — cost model, protocol
    links, and grid axes are all spec fields — which is exactly what
    lets a rebuild cross a process boundary
    (:func:`build_surfaces_from_spec`)."""
    if isinstance(protocols, Mapping):
        proto_pairs = tuple(protocols.items())
    else:
        proto_pairs = tuple(protocols)
    combine = "max" if cost_model.objective == "bottleneck" else "sum"
    return PlanSpec(
        solver=solver, backend=backend, combine=combine,
        scenario=ScenarioRef(kind="surface"),
        n_devices=tuple(int(n) for n in sizes),
        energy_budget=_norm_budget(energy_budget),
        variants=_norm_variants(variants),
        accuracy_floor=(None if accuracy_floor is None
                        else float(accuracy_floor)),
        cost_model=cost_model,
        protocols=proto_pairs,
        surface=SurfaceAxes(
            pt_scale=tuple(float(s) for s in pt_scale),
            loss_p=_norm_loss(loss_p),
            chunk_candidates=(None if chunk_candidates is None
                              else tuple(int(c) for c in chunk_candidates)),
        ),
        solver_options=(("beam_width", int(beam_width)),),
    )


# ---------------------------------------------------------------------------
# PlannerService — the execution tier
# ---------------------------------------------------------------------------


class PlannerService:
    """Resolves a :class:`PlanSpec` to the batched planning engines.

    Each method checks the operand against the spec, then calls the
    public entry point the spec describes
    (``solve_batched``/``solve_multi_channel``/``solve_variant_bank``,
    ``plan_split_batch``, ``build_surfaces``) with the spec's fields —
    so a spec solve and the equivalent kwargs call are the same code
    and bit-identical. Stateless and cheap: construct freely (one per
    call site is fine)."""

    # -- operand validation -------------------------------------------------
    @staticmethod
    def _check_operand(spec: PlanSpec, kind: str, shape=None) -> None:
        ref = spec.scenario
        if ref is None:
            return  # hand-built spec without a ref: trust the caller
        if ref.kind != kind:
            raise ValueError(f"spec scenario kind {ref.kind!r} does not "
                             f"match operand kind {kind!r}")
        if shape is not None and ref.shape is not None \
                and tuple(ref.shape) != tuple(shape):
            raise ValueError(f"spec scenario shape {ref.shape} does not "
                             f"match operand shape {tuple(shape)}")

    # -- solves over stacked tensors ---------------------------------------
    def solve(self, spec: PlanSpec, C):
        """Resolve a ``"tensor"`` spec against its stacked cost tensor."""
        self._check_operand(spec, "tensor", np.shape(C))
        return SW.solve_batched(
            C, solver=spec.solver, combine=spec.combine,
            backend=spec.backend, n_devices=spec.n_devices,
            **spec.options())

    def solve_multi_channel(self, spec: PlanSpec, C):
        """Resolve a ``"channels"`` spec against ``(ch, S, N, L, L)``."""
        self._check_operand(spec, "channels", np.shape(C))
        return SW.solve_multi_channel(
            C, channels=spec.channels or COST_CHANNELS,
            solver=spec.solver, combine=spec.combine, backend=spec.backend,
            n_devices=spec.n_devices, energy_budget=spec.energy_budget,
            channel_weights=spec.channel_weights,
            channel_combines=spec.channel_combines, **spec.options())

    def solve_variant_bank(self, spec: PlanSpec, C):
        """Resolve a ``"variant_bank"`` spec against ``(V, S, N, L, L)``."""
        self._check_operand(spec, "variant_bank", np.shape(C))
        return SW.solve_variant_bank(
            C, solver=spec.solver, combine=spec.combine,
            backend=spec.backend, n_devices=spec.n_devices,
            accuracy_proxy=spec.accuracy_proxy,
            accuracy_floor=spec.accuracy_floor, **spec.options())

    # -- cost-model batches --------------------------------------------------
    def plan(self, spec: PlanSpec, cost_models: Sequence[SplitCostModel]):
        """Resolve a ``"models"`` spec against its cost-model batch."""
        self._check_operand(spec, "models")
        if spec.scenario is not None and spec.scenario.count is not None \
                and spec.scenario.count != len(cost_models):
            raise ValueError(
                f"spec records {spec.scenario.count} cost models, got "
                f"{len(cost_models)}")
        n = spec.n_devices
        if n is None:
            raise ValueError("a 'models' spec needs n_devices")
        return PL.plan_split_batch(
            cost_models, n, solver=spec.solver, backend=spec.backend,
            energy_budget=spec.energy_budget, variants=spec.variants,
            accuracy_floor=spec.accuracy_floor, **spec.options())

    # -- surface families ----------------------------------------------------
    def build_surfaces(self, spec: PlanSpec):
        """Resolve a self-contained ``"surface"`` spec to the surface
        family ``{n_devices: DegradationSurface}``."""
        self._check_operand(spec, "surface")
        if spec.cost_model is None or spec.protocols is None \
                or spec.surface is None:
            raise ValueError("a 'surface' spec needs cost_model, protocols "
                             "and surface axes")
        opts = spec.options()
        return SF.build_surfaces(
            spec.cost_model, dict(spec.protocols), spec.n_devices,
            pt_scale=spec.surface.pt_scale, loss_p=spec.surface.loss_p,
            solver=spec.solver, backend=spec.backend,
            beam_width=int(opts.get("beam_width", 8)),
            chunk_candidates=spec.surface.chunk_candidates,
            energy_budget=spec.energy_budget, variants=spec.variants,
            accuracy_floor=spec.accuracy_floor)


# ---------------------------------------------------------------------------
# Process-boundary workers (module-level => picklable)
# ---------------------------------------------------------------------------


def build_surfaces_from_spec(spec: PlanSpec | str):
    """Build a surface family from a spec — THE process-pool rebuild
    worker. Module-level so ``ProcessPoolExecutor`` can pickle it;
    accepts either a :class:`PlanSpec` (pickled across the boundary) or
    its :meth:`~PlanSpec.to_json` payload. Returns the
    ``{n_devices: DegradationSurface}`` family, which pickles back to
    the parent for the ordinary generation/swap adoption path."""
    if isinstance(spec, str):
        spec = PlanSpec.from_json(spec)
    return PlannerService().build_surfaces(spec)


def solve_from_json(payload: str, C):
    """Solve a JSON-encoded ``"tensor"`` spec against ``C`` — the
    subprocess twin of :meth:`PlannerService.solve`, used by the
    spec-pickling parity test to prove an out-of-process solve is
    bitwise identical to the in-process one."""
    return PlannerService().solve(PlanSpec.from_json(payload), C)
