"""The planner's device programs compile for a TPU v5e chip, at real sizes.

No chip is attached: the TPU compiler compiles for a described ``v5e:2x2``
topology and refuses what the chip would refuse — a Pallas BlockSpec
Mosaic cannot tile, a kernel that overflows VMEM, a program that does not
fit HBM. Interpret-mode tests cannot see any of these.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and under pytest-xdist
every worker imports this file, so only the worker that runs these tests
may load it. Keep these tests in this one file for the same reason.
"""

from __future__ import annotations

import os

import pytest

# One TPU v5e chip: 16 GiB of HBM (Google Cloud documentation, "TPU v5e").
V5E_HBM_BYTES = 16 * 1024**3


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache out of it
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)
        compilation_cache.reset_cache()


def _compile(fn, *shapes):
    import jax

    return jax.jit(fn).lower(*shapes).compile()


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def _programs():
    """name -> (fn, operand shapes, is_pallas) at the sizes the chip runs.

    Dense Pallas reads a lane-padded ``C[S, 5, 128, 128]`` (2.5 GiB at
    S=8192; at S=100,000 it would need 30.5 GB); fused Pallas never
    builds ``C``, so it takes the ``iot-grid`` scale of S=100,000; the
    ``lax.scan`` DP runs the paper's MobileNetV2 depth (L=54) at
    S=16,384. The bottleneck (``max``) fused kernel runs 16 pipeline
    stages of DeepSeek-V3's 63 nodes (one lane tile, Lp=128) over a
    23,040-scenario pipeline what-if."""
    import jax.numpy as jnp

    from repro.core import pallas_dp as PD
    from repro.core import sweep as SW

    f32, i32 = jnp.float32, jnp.int32
    bs = PD.DEFAULT_BLOCK_S
    return {
        "dense_pallas": (
            PD._raw_pallas_fn("dense", "sum", bs, False),
            (((8192, 5, PD.LANE, PD.LANE), f32), ((8192, 1), i32)), True),
        "fused_pallas": (
            PD._raw_pallas_fn("fused", "sum", bs, False),
            (((5, PD.LANE, PD.LANE), f32), ((100_000, PD.LANE), f32),
             ((100_000, 1), i32)), True),
        "scan_dp": (
            SW._dp_jax_kernel("sum"),
            (((16_384, 5, 54, 54), f32), ((16_384,), i32)), False),
        "fused_pallas_bottleneck_16": (
            PD._raw_pallas_fn("fused", "max", bs, False),
            (((16, PD.LANE, PD.LANE), f32), ((23_040, PD.LANE), f32),
             ((23_040, 1), i32)), True),
    }


@pytest.mark.parametrize("name", ["dense_pallas", "fused_pallas", "scan_dp",
                                  "fused_pallas_bottleneck_16"])
def test_dp_program_compiles_for_v5e(one_chip, name):
    import jax

    fn, shapes, is_pallas = _programs()[name]
    compiled = _compile(fn, *(jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
                              for s, dt in shapes))
    used = _device_bytes(compiled)
    assert 0 < used < V5E_HBM_BYTES, f"{name}: {used / 2**30:.2f} GiB"
    if is_pallas:
        # a Mosaic kernel, not an XLA fallback
        assert "tpu_custom_call" in compiled.as_text(), name


def _named_solver(mode: str):
    """The jitted DP entry a backend dispatches, with operand shapes of
    two scenario blocks at ResNet50's depth."""
    import jax.numpy as jnp

    from repro.core import pallas_dp as PD
    from repro.core import sweep as SW

    f32, i32 = jnp.float32, jnp.int32
    S = 2 * PD.DEFAULT_BLOCK_S
    if mode == "scan":
        return SW._dp_jax_solver("sum"), (((S, 5, 52, 52), f32), ((S,), i32))
    fn = PD._pallas_dp_solver(mode, "sum", PD.DEFAULT_BLOCK_S, False)
    if mode == "fused":
        return fn, (((5, PD.LANE, PD.LANE), f32), ((S, PD.LANE), f32),
                    ((S, 1), i32))
    return fn, (((S, 5, PD.LANE, PD.LANE), f32), ((S, 1), i32))


@pytest.mark.parametrize("mode", ["fused", "dense", "scan"])
def test_dp_program_keeps_its_name_on_v5e(one_chip, mode):
    """The device trace prints the compiled module as ``jit_solve_<mode>``
    and a Pallas kernel's op as ``%solve_<mode>.n``; the benchmark's
    kernel readers match ``solve`` in them."""
    import re

    import jax

    fn, shapes = _named_solver(mode)
    text = fn.lower(*(jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
                      for s, dt in shapes)).compile().as_text()
    assert re.search(rf"^HloModule jit_solve_{mode}\b", text, re.M), mode
    if mode != "scan":
        assert re.search(rf"%solve_{mode}(\.\d+)? = .*custom-call\(", text), mode
