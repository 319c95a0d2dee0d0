"""Named spans of the planner's hot path, on the profiler's clock.

``span(name, **counts)`` is ``jax.profiler.TraceAnnotation("repro." +
name, **counts)``. While a JAX profiler trace runs, the span lands in
the same trace as the device's operations, on the same clock, and its
keyword arguments (and any later ``set_metadata``) arrive as the event's
stats. With no trace running it records nothing and costs about a
microsecond. The profiler is the recorder: there is no switch. Before
anything has imported JAX no trace can run, so a span then is a no-op
and a NumPy-only caller never pays JAX's half-second import for it.

Spans mark phases, never single scenarios: a ``sweep`` call opens about
twenty, whatever its grid size.

:data:`SPANS` lists every name the program emits, with what it covers
and the counts it carries.
"""

from __future__ import annotations

import sys

SPANS = {
    "repro.sweep": "one sweep() call, the SweepResult.wall_time_s interval; "
                   "counts scenarios",
    "repro.sweep.enumerate": "grid.scenarios() and grouping by model",
    "repro.sweep.build": "one group's grid build, the interval "
                         "SweepResult.build_time_s adds up",
    "repro.sweep.bank": "profile-bank matrices and bank_idx, one slot row "
                        "per distinct (mix, n_devices) gathered over the "
                        "group (_group_bank); counts slot_rows (those rows)",
    "repro.sweep.tx": "the group's transmission vectors, priced as arrays "
                      "over the group (_group_tx_vectors); counts "
                      "packet_vectors (the distinct (MTU, compression "
                      "factor) packet-count vectors)",
    "repro.sweep.gather": "dense paths: the cost tensor in one pass over "
                          "scenario blocks (gather, TX add, energy and "
                          "budget mask, cast to the DP's dtype); counts "
                          "blocks, workers, budgeted (rows with a finite "
                          "budget), masked (entries over budget)",
    "repro.sweep.rows": "one group's rows: whole-array pricing, SweepRow "
                        "construction and placement by index "
                        "(_group_rows); once more, the final tuple",
    "repro.dp": "a DP solve, solver entry to the wall_time_s stamp; on "
                "the fused bank path counts stacks (distinct device "
                "stacks with dead slots read as bank row 0) and launches "
                "(kernel launches made, one a joined live stack), and "
                "without all-k walked (the rows whose cost and splits "
                "were selected on the device)",
    "repro.dp.launch": "one kernel launch; counts kernel (its jitted name), "
                       "rows, rows_padded, lanes, lanes_padded, h2d_bytes, "
                       "d2h_bytes (padding included)",
    "repro.dp.prepare": "padding, the host-to-device copy and the dispatch",
    "repro.dp.fetch": "the wait for the kernel, the device-to-host copy and "
                      "the scatter of the unpadded outputs: the tables, or "
                      "on the walked path each row's cost and splits",
    "repro.dp.reconstruct": "the result on the host: tables to float64, "
                            "result selection and the split walk "
                            "(_reconstruct_splits), or on the walked path "
                            "the casts of the fetched costs and splits",
    "repro.plan.pipeline": "planner.pipeline_grid: the shapes' cost "
                           "profiles and the grid; counts shapes, layers, "
                           "mixes",
    "repro.plan.pipeline.profile": "one shape's layer graph and TPU cost "
                                   "profile; counts layers, "
                                   "experts_touched (a MoE layer, a step), "
                                   "sparse_layers (MLA blocks attending to "
                                   "an indexer's top-k, MTP's included), "
                                   "index_share_layers (those reusing an "
                                   "earlier layer's selection), "
                                   "linear_layers (nodes holding a "
                                   "recurrent state), state_bytes (that "
                                   "state, in its dtype) and cache_bytes "
                                   "(the KV or latent cache and index keys, "
                                   "bf16) the shape's graph holds, "
                                   "cache_read_bytes (what a step reads of "
                                   "that cache) and cut_index_bytes (the "
                                   "int32 top-k indices a cut before each "
                                   "sharing layer ships, summed over those "
                                   "cuts)",
    "repro.rebuild": "one in-process surface rebuild on the executor; "
                     "counts queued_ms (first queued request to build start)",
}


def span(name: str, **counts):
    """The ``repro.<name>`` span: use as ``with span("dp.launch",
    rows=n):``; ``set_metadata(**counts)`` on it adds counts known only
    later."""
    profiler = sys.modules.get("jax.profiler")
    if profiler is None:
        return _UNRECORDED
    return profiler.TraceAnnotation("repro." + name, **counts)


class _Unrecorded:
    """The span while no profiler can be running."""

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **counts):
        pass


_UNRECORDED = _Unrecorded()
