"""The main path's Pallas kernels compile for a TPU v5e.

Interpret mode (every other test) cannot see the chip's tiling and VMEM
rules, so these tests compile the kernels ahead of time for a *described*
``v5e:2x2`` topology — nothing runs, no chip is needed — at the shapes the
paper's pipeline and the IVF search use, with the committed v5e tile rows
(``kernels/tune_table.py``).  The topology is described inside a fixture,
never at import: only one process may load the TPU library at a time.
"""
import functools

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import TileError, tune_table
from repro.kernels.assign import assign_argmin_pallas
from repro.kernels.centroid import centroid_update_pallas
from repro.kernels.lloyd import lloyd_step_pallas
from repro.kernels.scan import adc_scan_pallas
from repro.kernels.tiles import pad_to

# the paper's headline job (500k x 2, n_sub=64, c=5, k=1000): 64 local
# partitions of 7,813 rows with 1,562 centers each, then the k=1000 merge
# over the 64 * 1,562 = 99,968-row pool (d pads to the 128-lane tile)
LOCAL = dict(parts=64, m=7_813, d=128, k=1_562)
MERGE = dict(m=99_968, d=128, k=1_000)
# a resident fit at the BIGANN width: 1,048,576 x 128 f32, k=1,024
EMBED = dict(m=1_048_576, d=128, k=1_024)
# IVF search, index_200k: 64 queries x nprobe 2 groups, ~1,000-slot
# lists, 64 subspaces x 8 bits
SCAN = dict(b=128, l=1_000, msub=64, c=256)


@pytest.fixture(scope="module")
def topo():
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # noqa: BLE001 — any failure means "no TPU lib"
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def table(topo):
    """The committed tile rows for the described device kind."""
    kind = topo.devices[0].device_kind
    return {kernel: tune_table.load_default(kernel, kind)
            for kernel in ("lloyd", "assign", "centroid", "scan")}


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()     # a kernel, compiled
    return compiled


def _points(one_chip, m, d, block_m, batch=()):
    mp = pad_to(m, block_m)
    return (jax.ShapeDtypeStruct(batch + (mp, d), jnp.float32,
                                 sharding=one_chip),
            jax.ShapeDtypeStruct(batch + (mp,), jnp.float32,
                                 sharding=one_chip))


@pytest.mark.parametrize("shape", [MERGE, EMBED], ids=["merge", "embed"])
@pytest.mark.parametrize("kernel", ["lloyd", "assign"])
def test_lloyd_and_assign_compile_for_v5e(one_chip, table, kernel, shape):
    cfg = table[kernel]
    assert cfg.block_m % 128 == 0
    x, w = _points(one_chip, shape["m"], shape["d"], cfg.block_m)
    c = jax.ShapeDtypeStruct((shape["k"], shape["d"]), jnp.float32,
                             sharding=one_chip)
    if kernel == "lloyd":
        fn = functools.partial(lloyd_step_pallas, block_m=cfg.block_m,
                               block_k=cfg.block_k, interpret=False)
        _compile(fn, x, w, c)
    else:
        fn = functools.partial(assign_argmin_pallas, block_m=cfg.block_m,
                               block_k=cfg.block_k, interpret=False)
        _compile(fn, x, c)


def test_local_stage_lloyd_vmapped_over_partitions_compiles(one_chip, table):
    cfg = table["lloyd"]
    p = LOCAL["parts"]
    x, w = _points(one_chip, LOCAL["m"], LOCAL["d"], cfg.block_m,
                   batch=(p,))
    c = jax.ShapeDtypeStruct((p, LOCAL["k"], LOCAL["d"]), jnp.float32,
                             sharding=one_chip)
    fn = jax.vmap(functools.partial(lloyd_step_pallas, block_m=cfg.block_m,
                                    block_k=cfg.block_k, interpret=False))
    _compile(fn, x, w, c)


def test_centroid_compiles_for_v5e(one_chip, table):
    cfg = table["centroid"]
    x, w = _points(one_chip, MERGE["m"], MERGE["d"], cfg.block_m)
    idx = jax.ShapeDtypeStruct(w.shape, jnp.int32, sharding=one_chip)
    fn = functools.partial(centroid_update_pallas, k=MERGE["k"],
                           block_m=cfg.block_m, interpret=False)
    _compile(fn, x, idx, w)


def test_scan_compiles_for_v5e(one_chip, table):
    luts = jax.ShapeDtypeStruct((SCAN["b"], SCAN["msub"], SCAN["c"]),
                                jnp.float32, sharding=one_chip)
    codes = jax.ShapeDtypeStruct((SCAN["b"], SCAN["l"], SCAN["msub"]),
                                 jnp.uint8, sharding=one_chip)
    fn = functools.partial(adc_scan_pallas, block_l=table["scan"].block_l,
                           interpret=False)
    _compile(fn, luts, codes)


@pytest.mark.parametrize("fn", ["jnp_assign", "min_sqdist"])
def test_jnp_distances_stay_f32_on_v5e(one_chip, fn):
    """The jnp reference paths compile to full-f32 matmuls and an f32
    nearest-center reduction: a bare argmin over the distance expansion
    compiles to a bf16 reduction on a v5e, and the default f32 matmul
    precision is a single bf16 pass."""
    from repro.core.backend import LloydBackend
    from repro.core.metrics import min_sqdist
    x = jax.ShapeDtypeStruct((4, LOCAL["m"], 2), jnp.float32,
                             sharding=one_chip)
    c = jax.ShapeDtypeStruct((4, LOCAL["k"], 2), jnp.float32,
                             sharding=one_chip)
    be = LloydBackend()
    f = {"jnp_assign": lambda x, c: be.assign(be.prepare(x), c),
         "min_sqdist": min_sqdist}[fn]
    text = jax.jit(jax.vmap(f)).lower(x, c).compile().as_text()
    assert "operand_precision={highest,highest}" in text
    assert "bf16[" not in text


def test_vmem_budget_refuses_wide_large_k_before_compiling():
    """d=768, K=4096 cannot keep the fused pass's (K, d) accumulator in
    the VMEM budget: the kernel and the planner both say so with a
    TileError naming the budget, before anything is compiled."""
    from repro.api import plan
    from repro.core.backend import PallasFusedBackend
    from repro.core.spec import ClusterSpec
    x = jnp.zeros((4096, 768), jnp.float32)
    with pytest.raises(TileError, match="MiB budget"):
        lloyd_step_pallas(x, jnp.ones((4096,)), jnp.zeros((4096, 768)),
                          block_m=512, block_k=256)
    spec = ClusterSpec.make(4096, backend=PallasFusedBackend.name)
    with pytest.raises(TileError, match="MiB budget"):
        plan(spec, data_shape=(1_000_000, 768))
