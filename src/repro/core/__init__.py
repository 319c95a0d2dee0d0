"""Core: the paper's contribution — split-latency model, solvers, planner.

Public API (documented in ``docs/api.md``; layer map in
``docs/architecture.md``):
  latency    — Eq. 4-8 cost model (LinkProfile / DeviceProfile / SplitCostModel)
  spec       — the planner tier: PlanSpec (one serializable planning
               request; exact JSON round-trip), PlannerService (spec ->
               the public entry point it describes),
               build_surfaces_from_spec (process-pool rebuild worker)
  solvers    — beam / greedy / first_fit / random_fit / brute_force / optimal_dp
  planner    — plan_split (IoT), plan_pipeline (TPU PP), compare_solvers,
               plan_split_batch (vectorized fleet planning, heterogeneous
               fleet sizes + device mixes)
  sweep      — batched solvers over stacked C[k,a,b] cost tensors +
               ScenarioGrid fleet sweeps (protocol x mix x fleet x loss
               x rate x compression), all-k beam, per-scenario fleet-size
               vectors, variant-bank solves + Pareto frontier emission
  shard      — scenario-axis sharding over the local JAX device mesh
               (shard_map + pad/unpad; backend="sharded" everywhere the
               batched DP runs)
  pallas_dp  — Pallas kernel fusing cost-tensor construction with the
               DP recurrence in scenario tiles (backend="pallas"; C is
               never materialized; interpret mode off-TPU)
  surface    — precomputed degradation surfaces (per-protocol packet-time
               x loss grids -> best plan + switch points + interpolation)
               for O(1) adaptive replanning; build_surfaces solves every
               fleet size in one batched pass
  async_replan — stale-while-revalidate surface rebuilds: SurfaceRebuilder
               runs re-centered build_surfaces on a background executor,
               generation-versioned atomic swap-on-ready
  adaptive   — LinkEstimator + AdaptiveSplitManager runtime replanning;
               fleet_managers for mixed-fleet-size deployments
  profiles   — paper-calibrated ESP32 + protocol tables; TPU v5e constants
  executor   — run_split / run_unsplit segment execution with wire simulation
  quantization — int8 PTQ + activation wire format
"""

from repro.core.latency import (  # noqa: F401
    COST_CHANNELS,
    BottleneckVariant,
    ContentionModel,
    DeviceProfile,
    LayerCost,
    LinkProfile,
    ModelCostProfile,
    RTTBreakdown,
    SplitCostModel,
    bottleneck_variant,
    bottleneck_variants,
    rtt_breakdown,
)
from repro.core.planner import (  # noqa: F401
    SegmentPlan,
    SplitPlan,
    compare_solvers,
    pipeline_grid,
    plan_pipeline,
    plan_split,
    plan_split_batch,
    plan_surface,
    tpu_cost_profile,
    uniform_split,
)
# NOTE: like sweep below, `repro.core.surface` must keep resolving to the
# submodule — only names are re-exported here, never a shadowing function.
from repro.core.surface import (  # noqa: F401
    DegradationSurface,
    ProtocolSurface,
    SurfaceLookup,
    SwitchPoint,
    build_surface,
    build_surfaces,
    refit_link,
)
# NOTE: the sweep() entry point itself is deliberately NOT re-exported
# here — `repro.core.sweep` must keep resolving to the submodule
# (`from repro.core.sweep import sweep` for the function).
from repro.core.sweep import (  # noqa: F401
    DP_BACKENDS,
    BatchedSolverResult,
    ParetoFrontier,
    Scenario,
    ScenarioGrid,
    SweepResult,
    SweepRow,
    batched_beam_search,
    batched_beam_search_all_k,
    batched_greedy_search,
    batched_greedy_search_all_k,
    batched_optimal_dp,
    batched_total_cost,
    apply_accuracy_floor,
    apply_energy_budget,
    combine_channels,
    pareto_frontier,
    solve_multi_channel,
    solve_variant_bank,
    stack_cost_tensors,
    sweep_scalar,
)
# NOTE: `repro.core.shard` likewise stays a submodule attribute (it
# imports sweep, so it must come after it here). Importing these names
# is cheap — JAX loads lazily, on the first sharded solve.
from repro.core.shard import (  # noqa: F401
    scenario_shards,
    sharded_dp_tables,
    sharded_optimal_dp,
)
# NOTE: `repro.core.pallas_dp` likewise stays a submodule attribute (it
# imports sweep too). JAX/Pallas load lazily, on the first pallas solve.
from repro.core.pallas_dp import (  # noqa: F401
    pallas_dp_tables,
    pallas_fused_dp_tables,
    pallas_fused_optimal_dp,
    pallas_interpret_default,
    pallas_optimal_dp,
)
from repro.core.solvers import (  # noqa: F401
    SOLVERS,
    SolverResult,
    VariantInstance,
    beam_search,
    brute_force,
    budget_masked,
    first_fit_search,
    greedy_search,
    optimal_dp,
    random_fit,
    total_cost,
    total_energy,
)
# NOTE: `repro.core.spec` sits above the engines it resolves to
# (sweep/planner/surface import it nowhere), so it comes after them.
from repro.core.spec import (  # noqa: F401
    PlanSpec,
    PlannerService,
    ScenarioRef,
    SurfaceAxes,
    build_surfaces_from_spec,
)
# NOTE: `repro.core.async_replan` likewise stays a submodule attribute;
# it imports surface, so it must come after it (and before adaptive,
# which imports it).
from repro.core.async_replan import (  # noqa: F401
    ManualExecutor,
    RebuildFanout,
    RebuildHandle,
    RebuildRequest,
    SurfaceRebuilder,
    recentered_axes,
)
# NOTE: `repro.core.adaptive` likewise stays a submodule attribute; it
# imports planner/surface/sweep/async_replan, so it must come after
# them here.
from repro.core.adaptive import (  # noqa: F401
    AdaptiveSplitManager,
    LinkEstimator,
    PlanDecision,
    fleet_managers,
    optimize_chunk_size,
    surface_parity_report,
)
