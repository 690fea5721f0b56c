"""Compile the main path's Pallas kernels, the planner program, the
reservoirs' membership searches and the fleet step for a described,
unattached TPU v5e chip at the smoke test's widths (M = 65,536 streams,
W = 1,024 docs per chunk, K = 1,024; the logmem scan also at W = 8,192). Nothing runs: the TPU compiler refuses
what the chip would refuse — block tiling, VMEM, device memory.

The topology is described inside a module fixture (only one process may
load the TPU library, and the test workers all import this file), and
the persistent compile cache is off around these compiles: a TPU
program written to it cannot be read back without the chip."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import jaxcompat, shp_jax
from repro.kernels.batched_topk.batched_topk import batched_topk_pallas
from repro.kernels.logmem_update.logmem_update import logmem_admit_pallas
from repro.kernels.tier_assign.tier_assign import tier_assign_pallas
from repro.kernels.topk_filter.topk_filter import topk_filter_pallas
from repro.streams import engine, logmem

M, W, K = 65_536, 1_024, 1_024
LM, LW, LK = 64, 8_192, 65_536


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    from jax.sharding import Mesh
    from repro.parallel import fleet
    return Mesh(np.array(topo.devices), (fleet.FLEET_AXIS,))


@pytest.fixture
def compiled_kernels(monkeypatch):
    """The kernel wrappers ask the default backend (here the CPU) whether
    to interpret: steer them to the compiled TPU path."""
    monkeypatch.setattr(jaxcompat, "pallas_interpret", lambda: False)


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernels(compiled):
    return compiled.as_text().count("tpu_custom_call")


def test_batched_topk_compiles(one_chip):
    c = jax.jit(lambda s, t: batched_topk_pallas(s, t)).lower(
        _spec(one_chip, (M, W)), _spec(one_chip, (M,))).compile()
    assert _kernels(c)


def test_topk_filter_compiles(one_chip):
    c = jax.jit(lambda s, t: topk_filter_pallas(s, t)).lower(
        _spec(one_chip, (M * 16,)), _spec(one_chip, ())).compile()
    assert _kernels(c)


@pytest.mark.parametrize("m,w", [(M, W), (M, LW)])
def test_logmem_update_compiles(one_chip, m, w):
    c = jax.jit(lambda s, i, t: logmem_admit_pallas(s, i, t)).lower(
        _spec(one_chip, (m, w)), _spec(one_chip, (m, w), jnp.int32),
        _spec(one_chip, (m,))).compile()
    assert _kernels(c)


def test_tier_assign_compiles(one_chip):
    c = jax.jit(lambda i, b, f: tier_assign_pallas(i, b, f, n_tiers=3)
                ).lower(_spec(one_chip, (M, K), jnp.int32),
                        _spec(one_chip, (M, 2), jnp.int32),
                        _spec(one_chip, (M,), jnp.int32)).compile()
    assert _kernels(c)


@pytest.mark.parametrize("constrained", [False, True])
def test_planner_with_plan_solve_compiles(one_chip, compiled_kernels,
                                          constrained):
    """One float32 fleet chunk of the 3-tier device planner, reduced by
    the ``plan_solve`` kernel (the TPU configuration)."""
    rows = shp_jax._CHUNK_M
    mt, m1 = _spec(one_chip, (rows, 3)), _spec(one_chip, (rows,))
    c = shp_jax._plan_jit.lower(
        mt, mt, mt, m1, m1, m1, mt, mt, m1, t=3, constrained=constrained,
        capfin=(constrained, constrained, False), slo_any=constrained,
        use_pallas=True).compile()
    assert _kernels(c)


@pytest.mark.parametrize("kernel", [False, True])
def test_fleet_step_compiles(one_chip, compiled_kernels, kernel):
    """The donating fleet step with device metrics and the meter fold
    over an exact bucket and a logmem bucket: the default jnp path, and
    the Pallas filter."""
    step = engine._make_step(kernel, 512, bucket_ks=(K, LK),
                             with_metrics=True, donate=True,
                             bucket_engines=("exact", "logmem"))
    states = (jax.eval_shape(lambda: engine.init(M, K)),
              jax.eval_shape(lambda: logmem.init(LM)))
    states = jax.tree_util.tree_map(
        lambda x: _spec(one_chip, x.shape, x.dtype), states)
    batches = ((_spec(one_chip, (M, W)), _spec(one_chip, (M, W), jnp.int32)),
               (_spec(one_chip, (LM, LW)),
                _spec(one_chip, (LM, LW), jnp.int32)))
    from repro.obs import metrics
    mstate = jax.tree_util.tree_map(
        lambda x: _spec(one_chip, np.shape(x), jnp.asarray(x).dtype),
        metrics.init())
    from repro.streams import metering
    meters = tuple(
        metering.MeterState(
            bounds=_spec(one_chip, (m, 2), jnp.int32),
            floor=_spec(one_chip, (m,), jnp.int32),
            migrate=_spec(one_chip, (m,), jnp.bool_),
            observed=_spec(one_chip, (m,), jnp.int32)) for m in (M, LM))
    c = step.lower(states, batches, (), mstate, meters).compile()
    assert (_kernels(c) > 0) == kernel
    mem = c.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used < 16e9, used


@pytest.mark.parametrize("w", [64, 512])
def test_narrow_filtered_update_compiles(one_chip, compiled_kernels, w):
    """Narrow batches (W < K) take the filtered update too: its Pallas
    scan compiles below one column block, and the merge moves no entry
    through an index."""
    st = jax.tree_util.tree_map(
        lambda x: _spec(one_chip, x.shape, x.dtype),
        jax.eval_shape(lambda: engine.init(M, K)))
    c = jax.jit(lambda *a: engine.filtered_update(*a, use_pallas=True)
                ).lower(st, _spec(one_chip, (M, w)),
                        _spec(one_chip, (M, w), jnp.int32)).compile()
    assert _kernels(c)
    assert " gather(" not in c.as_text()
    assert " scatter(" not in c.as_text()


@pytest.mark.parametrize("rows,n,h,method", [(M, W, K, "compare"),
                                              (LM, LK // 2, LK // 2, "sort")])
def test_member_compiles_without_loop(one_chip, rows, n, h, method):
    """Both membership searches over a fleet's rows: no ``while`` loop of
    gathers, and scratch memory grows with the values, not with the
    (N, H) pairs (the compare's mask is never stored)."""
    from repro.core import topk
    assert topk.member_method(n, h) == method
    c = jax.jit(jax.vmap(topk.member)).lower(
        _spec(one_chip, (rows, n), jnp.int32),
        _spec(one_chip, (rows, h), jnp.int32)).compile()
    assert " while(" not in c.as_text()
    assert c.memory_analysis().temp_size_in_bytes < rows * (n + h) * 32


def test_sharded_plan_compiles(four_chips, compiled_kernels):
    """The fleet plan ``shard_map``-ped over a 2x2 mesh, ``plan_solve``
    inside each shard."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.parallel import fleet
    row = NamedSharding(four_chips, P(fleet.FLEET_AXIS))
    fn = shp_jax._plan_sharded_fn(four_chips, 3, False, (False,) * 3, False,
                                  True)
    mt, m1 = _spec(row, (M // 4, 3)), _spec(row, (M // 4,))
    assert _kernels(fn.lower(mt, mt, mt, m1, m1, m1, mt, mt, m1).compile())


def test_waterfill_f64_compiles(four_chips):
    """The float64 psum bisection of the shared-capacity water-fill (the
    TPU lowers only sum all-reduces in f64)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.parallel import fleet
    with jaxcompat.enable_x64():
        fn = fleet._waterfill_fn(four_chips)
        c = fn.lower(
            _spec(NamedSharding(four_chips, P(fleet.FLEET_AXIS)), (M,),
                  jnp.float64),
            _spec(NamedSharding(four_chips, P()), (), jnp.float64)).compile()
    assert "all-reduce" in c.as_text()
