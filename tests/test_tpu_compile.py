"""Compile-only checks of the Pallas data plane for a TPU v5e.

The kernels are lowered (``interpret=False``) and compiled for a
described ``v5e:2x2`` topology, without a chip: the compiler refuses
here what it would refuse on the chip (unsupported primitives, layouts
Mosaic cannot infer, integer matmuls), at zero chip time. Sizes are
those ``chip_smoke.py`` runs on the chip. Nothing is executed.

The topology is described inside a module fixture (never at import), so
every test worker collects the same tests and only the worker that runs
this file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src.lib import xla_client

from repro.kernels import bucket_route as kbucket
from repro.kernels import ops as kops
from repro.kernels import segment_combine as kseg

M, Q, W = 1 << 20, 8, 8            # routed messages per worker, lanes
E, N = 1 << 22, 1 << 19            # sorted edges, segments per worker


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip: keep it out of the cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()
    if log_dir is None:
        os.environ.pop("TPU_LOG_DIR", None)
    else:
        os.environ["TPU_LOG_DIR"] = log_dir


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_bucket_ranks_compiles_for_v5e(one_chip):
    exe = _compile(
        lambda k: kbucket.bucket_ranks_pallas(k, num_buckets=W,
                                              interpret=False),
        _spec(one_chip, (M,), jnp.int32))
    assert "tpu_custom_call" in exe.as_text()


def test_bucket_ranks_lanes_compiles_for_v5e(one_chip):
    exe = _compile(
        lambda k, l: kbucket.bucket_ranks_lanes_pallas(
            k, l, num_buckets=W, interpret=False),
        _spec(one_chip, (M,), jnp.int32),
        _spec(one_chip, (M, Q), jnp.int32))
    assert "tpu_custom_call" in exe.as_text()


@pytest.mark.parametrize("combiner,dtype,d", [
    ("min", jnp.int32, 1),
    ("sum", jnp.float32, 1),
    ("min", jnp.int32, 8),
], ids=["int32-min", "f32-sum", "int32-min-d8"])
def test_segment_combine_compiles_for_v5e(one_chip, combiner, dtype, d):
    """The work-list kernel at the chip's sizes, with its grid at the
    bound NB + EC; the compiled call keeps the operand signature that
    ``benchmarks/chip/kernel_cost.py`` reads its roofline share from."""
    from benchmarks.chip import kernel_cost

    block_rows, block_edges = kops.autotune_block_sizes(N, E)
    items = N // block_rows + E // block_edges

    def combine(vals, seg, item_block, item_chunk):
        return kseg.segment_combine_pallas(
            vals, seg, item_block, item_chunk, num_segments=N,
            combiner=combiner, block_rows=block_rows,
            block_edges=block_edges, interpret=False)

    exe = _compile(combine, _spec(one_chip, (E, d), dtype),
                   _spec(one_chip, (E,), jnp.int32),
                   _spec(one_chip, (items,), jnp.int32),
                   _spec(one_chip, (items,), jnp.int32))
    # the trace names a device op by its HLO text with operand shapes
    options = xla_client._xla.HloPrintOptions.short_parsable()
    options.print_operand_shape = True
    hlo = exe.runtime_executable().hlo_modules()[0].to_string(options)
    calls = [line for line in hlo.splitlines() if "tpu_custom_call" in line]
    assert calls
    costs = [kernel_cost.segment_combine(*kernel_cost.signature(line))
             for line in calls]
    moved = E * 4 + E * d * 4 + N * d * 4  # ids, values, one output row
    assert (moved, E * d) in costs


@pytest.mark.parametrize("key,serve", [("sv:composed", False),
                                       ("reach:basic", True)])
def test_main_path_loop_compiles_for_v5e(one_chip, monkeypatch, key, serve):
    """The fused sv:composed loop and the reach:basic serving chunk, with
    the kernels lowered for the chip inside them (small graph: the
    layouts, not the sizes, are what this checks)."""
    from repro.algorithms import REGISTRY
    from repro.graph import pgraph
    from repro.pregel import runtime

    # the CPU backend would pick interpret mode; this compile is for TPU
    monkeypatch.setattr(kops, "resolve_interpret", lambda interpret=None:
                        False)
    spec = REGISTRY[key]
    graph = spec.make_graph(10, 0)
    pg = pgraph.partition_graph(graph, W, "random", build=spec.build)
    prog = spec.make(graph, 0)
    if serve:
        one = prog.query_init(pg, spec.queries(graph, 0, 1)[0])
        state0 = jax.tree_util.tree_map(
            lambda leaf: jnp.repeat(leaf[:, None], Q, axis=1), one)
    else:
        state0 = prog.init(pg)
    as_spec = lambda x: _spec(one_chip, np.shape(x), jnp.result_type(x))
    exe = runtime.compile_supersteps(
        jax.tree_util.tree_map(as_spec, pg), prog.step,
        jax.tree_util.tree_map(as_spec, state0), max_steps=prog.max_steps,
        channels=prog.channels, use_kernel=True,
        mode="chunked" if serve else "fused", chunk_size=4,
        num_queries=Q if serve else None, serve=serve)
    assert exe.use_kernel
    assert "tpu_custom_call" in exe._fn.as_text()
