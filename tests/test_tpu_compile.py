"""Compile the twin's main programs for a described TPU v5e, on the CPU.

The TPU compiler ships with jaxlib's TPU support and compiles for a chip
that is described rather than attached (``jax.experimental.topologies``),
so these tests catch what the chip's compiler would refuse — unaligned
Pallas blocks, a sharded program that does not partition, a program that
does not fit — without a chip. Nothing runs here: results and times come
only from ``chip_smoke.py`` on the chip.

The topology is described inside a module fixture, never at import time,
so a test worker that only collects this file never loads the TPU
library. Every compile lives in this one file: under ``--dist loadfile``
(the tier-1 command) one worker runs them all and loads the library
once. The fixture skips only where jaxlib has no TPU support installed;
any other failure to describe the chip fails the tests.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.core import engine as eng
from repro.core import types as T
from repro.cooling import model as cool
from repro.datasets import loaders
from repro.kernels.power_topo import ops
from repro.obs import phases
from repro.obs import timing
from repro.systems.config import get_system

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")
DAY_S = 86400.0


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except RuntimeError as e:
        if "TPU support not installed" not in str(e):
            raise
        pytest.skip(f"no TPU compiler in this install: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache off here
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def frontier():
    """Frontier at its published 9,600 nodes with ``load_frontier()``
    defaults (1,238 jobs over one day, 96-sample power profiles)."""
    system = get_system("frontier")
    js = loaders.load_frontier()
    js.assign_prepop_placement(0.0, system.n_nodes)
    table = js.to_table()
    return system, table, eng.init_state(system, table, 0.0, DAY_S)


def _shapes(tree, sharding):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x),
                                       sharding=sharding), tree)


def test_frontier_scan_compiles_for_one_chip(topo, frontier):
    system, table, st0 = frontier
    one = SingleDeviceSharding(topo.devices[0])
    n_steps = int(DAY_S / system.dt)
    traced = dict(timing.RELEASE_STATS)
    compiled = eng._runner(system, n_steps).lower(
        _shapes(table, one), _shapes(st0, one),
        _shapes(T.Scenario.make("fcfs", "easy"), one), None, None).compile()
    # without grid signals the runner releases nodes by their end time
    assert timing.RELEASE_STATS["node_end"] > traced["node_end"]
    assert timing.RELEASE_STATS["job_gather"] == traced["job_gather"]
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 16 * 2**30
    assert n_steps == 5760
    # the chip's compiler keeps the phase scopes, so a trace's device time
    # reads per phase; the node cumsum it rewrites without a name stays
    # with the admission loop that runs it
    ins = phases.instructions(compiled.as_text())
    assert {i.phase for i in ins if i.depth > 0} >= set(phases.PHASES) - {
        phases.FAILURES}
    assert {i.phase for i in ins if i.opcode == "reduce-window"
            and i.depth > 1} == {phases.ADMISSION}
    # power and heat per CDU come from the per-group occupancy: no gather
    # spreads a per-job value over the nodes there
    assert not node_wide_gathers(compiled.as_text(), system.n_nodes,
                                 {phases.POWER, phases.COOLING})
    # release compares each node's end time with the clock: no per-job
    # flag (pred) or value is gathered onto the nodes there either
    assert not node_wide_gathers(compiled.as_text(), system.n_nodes,
                                 {phases.PREPARE})


_GATHER = re.compile(r"=\s*[a-z]\w*\[([\d,]*)\]\S*\s+gather\(")


def node_wide_gathers(text, n_nodes, phase_set):
    """Gathers (fused or not) named in one of ``phase_set`` whose
    output, of any element type, has a multiple of ``n_nodes`` elements:
    per-node values, or S rows of them under a vmap."""
    found = []
    for line in text.splitlines():
        m = _GATHER.search(line)
        if m is None:
            continue
        size = int(np.prod([int(d) for d in m.group(1).split(",") if d]))
        op = re.search(r'op_name="([^"]*)"', line)
        if (size % n_nodes == 0 and op
                and phases.phase_of(op.group(1)) in phase_set):
            found.append(line.strip()[:160])
    return found


@pytest.mark.parametrize("kernel", ["group_power", "fused_cooling_hier"])
def test_power_topo_kernel_compiles_at_frontier_width(topo, kernel):
    system = get_system("frontier")
    cfg = system.cooling
    N, G, H = system.n_nodes, cfg.n_groups, cfg.topology.n_halls
    one = SingleDeviceSharding(topo.devices[0])
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                              sharding=one)
    S = 8
    if kernel == "group_power":
        fn = lambda x: ops.group_power(x, G, use_pallas=True)
        args = (f32(S, N),)
    else:
        p = cool.cdu_params(cfg, system.dt)
        fn = lambda x, ts, md, tb, tset: ops.fused_cooling_hier(
            x, ts, md, tb, tset, cfg.hall_of_group(), G, p,
            use_pallas=True)
        args = (f32(S, N), f32(S, G), f32(S, G), f32(S, H), f32(S))
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def test_sharded_sweep_compiles_on_four_chips_without_collectives(
        topo, frontier):
    system, table, st0 = frontier
    mesh = eng.sweep_mesh(topo.devices)
    assert mesh.devices.size == 4
    rep = NamedSharding(mesh, PartitionSpec())
    rows = NamedSharding(mesh, eng.scenario_spec())
    pairs = [("fcfs", "none"), ("fcfs", "easy"), ("sjf", "first-fit"),
             ("ljf", "easy"), ("priority", "easy"), ("sjf", "easy"),
             ("fcfs", "first-fit"), ("replay", "none")]
    scens = T.stack_scenarios([T.Scenario.make(p, b) for p, b in pairs])
    run = eng._runner(system, int(DAY_S / system.dt), axes=(None, None),
                      mesh=mesh)
    compiled = run.lower(_shapes(table, rep), _shapes(st0, rep),
                         _shapes(scens, rows), None, None).compile()
    text = compiled.as_text()
    found = [c for c in COLLECTIVES if c in text]
    assert not found, f"scenario rows must not communicate: {found}"
