"""Compile the main path for a described TPU v5e chip, without the chip.

The TPU compiler refuses what interpret mode accepts (unlowerable
primitives, misaligned tiles, programs that do not fit), so these tests
compile the explorer's batched-fitness program with its Pallas kernel and
the rwkv6-3b serving programs at published widths for one v5e chip. The
topology is described inside a fixture, never at import: only one process
may load the TPU library, and every test worker imports this file.
"""
import functools
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, SingleDeviceSharding

V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a described chip cannot read the persistent cache back, so programs
    # compiled for it are kept out of it
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    from jax.experimental import topologies
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        jax.config.update("jax_enable_compilation_cache", prev)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def native_pallas(monkeypatch):
    """This process runs on the CPU, where kernels default to interpret
    mode; the compiles below are for the chip, where they never do."""
    import repro.kernels.wavefront as wavefront
    monkeypatch.setattr(wavefront, "pallas_interpret", lambda: False)


@pytest.fixture(scope="module")
def explorer_fitness():
    """The explorer's device path on resnet18 ("tile", 32, 1) / mc_hetero:
    the score program with its Pallas kernel."""
    from repro.api.session import ExplorationSession
    from repro.configs.paper_workloads import resnet18
    from repro.core.vectorized import BatchedFitness
    from repro.hw.catalog import mc_hetero
    engine = ExplorationSession().engine(resnet18(), mc_hetero(),
                                         ("tile", 32, 1))
    return BatchedFitness(engine, use_pallas=True)


def _struct(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("pop", [32, 256])
def test_serialize_prefix_compiles_at_explorer_shapes(
        one_chip, native_pallas, explorer_fitness, pop):
    """One population chunk's per-wavefront queue update: the smoke run's
    chunk (24 genomes padded to 32) and the largest (`max_batch`)."""
    from repro.kernels.wavefront import serialize_prefix
    bf = explorer_fitness
    assert bf.width == 17
    for rows in (bf.n_cores, bf.n_chan):
        free = _struct((pop, rows), jnp.float32, one_chip)
        items = _struct((pop, rows, bf.width), jnp.float32, one_chip)
        compiled = jax.jit(serialize_prefix).lower(free, items,
                                                   items).compile()
        assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture(scope="module")
def llm_fitness():
    """The explorer's device path on DeepSeek-V2-Lite's prefill ("tile",
    8, 1) / mc_hetero: 4816 CNs, wavefronts up to 134 wide."""
    from repro.api.session import ExplorationSession
    from repro.configs.paper_workloads import deepseek_v2_lite_prefill
    from repro.core.vectorized import BatchedFitness
    from repro.hw.catalog import mc_hetero
    engine = ExplorationSession().engine(deepseek_v2_lite_prefill(),
                                         mc_hetero(), ("tile", 8, 1))
    return BatchedFitness(engine, use_pallas=True)


def test_serialize_prefix_compiles_at_llm_width(one_chip, native_pallas,
                                                llm_fitness):
    """The kernel at the LLM cell's wavefront width, 134 lanes: not a
    multiple of 128, which the lane rolls must still lower for."""
    from repro.kernels.wavefront import serialize_prefix
    bf = llm_fitness
    assert bf.width == 134
    for rows in (bf.n_cores, bf.n_chan):
        free = _struct((32, rows), jnp.float32, one_chip)
        items = _struct((32, rows, bf.width), jnp.float32, one_chip)
        compiled = jax.jit(serialize_prefix).lower(free, items,
                                                   items).compile()
        assert "tpu_custom_call" in compiled.as_text()


def test_batched_fitness_score_program_compiles(one_chip, native_pallas,
                                                explorer_fitness):
    bf = explorer_fitness
    genomes = _struct((32, bf.n_layers), jnp.int32, one_chip)
    compiled = bf._score_fn.lower(genomes).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_llm_fitness_program_has_no_large_per_element_gather(
        one_chip, native_pallas, llm_fitness):
    """The LLM cell's 32-row fitness program looks its core-indexed tables
    up by selection: no gather of single elements (`offset_dims={}`) as
    large as one value per CN and row is left. Row gathers, which copy
    whole 32-row slices, stay; so does the segment barrier's (W, P) one."""
    bf = llm_fitness
    rows = 32
    genomes = _struct((rows, bf.n_layers), jnp.int32, one_chip)
    hlo = bf._score_fn.lower(genomes).compile().as_text()
    assert "tpu_custom_call" in hlo
    large = []
    for line in hlo.splitlines():
        if " gather(" not in line or "offset_dims={}" not in line:
            continue
        dims = re.search(r"= \w+\[([\d,]*)\]", line).group(1)
        if math.prod(int(d) for d in dims.split(",") if d) >= (
                bf.n + 1) * rows:
            large.append(line.split(" gather(")[0].strip())
    assert large == []


@pytest.mark.parametrize("program", ["prefill", "decode_step"])
def test_rwkv6_3b_serving_program_compiles(topo, one_chip, program):
    """The serving engine's two programs at rwkv6-3b's published widths with
    the smoke run's batch (4 slots, 128-token prompts, 168-token cache)
    fit one chip."""
    from repro.configs import ARCHS
    from repro.models import zoo
    from repro.models.module import abstract_from_specs
    cfg = ARCHS["rwkv6-3b"]
    slots, prompt_len, max_len = 4, 128, 168
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)

    def on_chip(tree):
        return jax.tree.map(lambda s: _struct(s.shape, s.dtype, one_chip),
                            tree)

    params = on_chip(abstract_from_specs(zoo.build_param_specs(cfg)))
    caches = on_chip(abstract_from_specs(
        zoo.build_cache_specs(cfg, slots, max_len)))
    if program == "prefill":
        fn = functools.partial(zoo.prefill, cfg, mesh=mesh)
        args = (params,
                {"tokens": _struct((slots, prompt_len), jnp.int32, one_chip)},
                caches)
    else:
        fn = functools.partial(zoo.decode_step, cfg, mesh=mesh)
        args = (params, _struct((slots, 1), jnp.int32, one_chip), caches,
                _struct((), jnp.int32, one_chip))
    with jax.set_mesh(mesh):
        compiled = jax.jit(fn, donate_argnums=(2,)).lower(*args).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used < V5E_HBM_BYTES
