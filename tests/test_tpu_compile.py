"""The main-path Pallas kernels compile for a TPU v5e at serving widths.

Nothing runs on a chip here: each test lowers one kernel for a described
(not attached) ``v5e:2x2`` topology and compiles it with the TPU compiler,
which refuses what interpret mode accepts — blocks whose tiling breaks
Mosaic's (8, 128) rule or XLA's layout, and kernels that overrun the
16 MiB of scoped VMEM.  Widths are the serving path's: 2-D points padded
to ``COORD_PAD`` lanes, datasets padded to 1024 points (T-Drive's 100-1000
point trajectories), 16,384 repository slots, drains up to the 256-row
bucket, the ExactHaus chunk of 32, and the fused bound grid over the 15
nodes of tree levels 0..3 (``refine_levels=3``) and over the wider levels
a query may ask for (up to 127 nodes, whose reductions loop).  Tiles are the routing
defaults in ``kernels/autotune.DEFAULTS``.

The topology is described inside a module fixture (never at import), so
under several pytest workers only the worker that runs this file loads
the TPU library.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import autotune
from repro.kernels import bound_matrix, hausdorff, nn_distance, set_intersect

W = hausdorff.COORD_PAD        # 2-D points padded to the kernel lane width
N_PTS = 1024                   # dataset slot: 16-point leaves x 2**6
SLOTS = 16384                  # T-Drive's 10,357 datasets, padded
DRAIN = 256                    # largest engine bucket
CHUNK = 32                     # QueryEngine.default_chunk
SIG_WORDS = 32                 # theta=5 z-order signature words
# bound-grid levels 0..6; serving refines levels 0..3 (15 nodes)
LEVELS = ((0, 1), (1, 3), (3, 7), (7, 15), (15, 31), (31, 63), (63, 127))


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no TPU compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-topology compile cannot be read back from the persistent
    # cache without a chip; keep these compiles out of it
    from jax.experimental.compilation_cache import compilation_cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo
    return hlo


def _tiles(op):
    cfg = autotune.DEFAULTS[op]
    return cfg.tq, cfg.td


def test_min_sq_dists_compiles(one_chip):
    tq, td = _tiles("directed_hausdorff")
    _compile(functools.partial(hausdorff.min_sq_dists, n_coords=2,
                               tq=tq, td=td), one_chip,
             ((N_PTS, W), jnp.float32), ((N_PTS, W), jnp.float32),
             ((N_PTS,), jnp.bool_))


def test_min_sq_dists_grid_compiles(one_chip):
    tq, td = _tiles("hausdorff_grid")
    _compile(functools.partial(hausdorff.min_sq_dists_grid, n_coords=2,
                               tq=tq, td=td), one_chip,
             ((8, 256, W), jnp.float32), ((8, CHUNK, N_PTS, W), jnp.float32),
             ((8, CHUNK, N_PTS), jnp.bool_))


def test_nn_sq_dists_compiles(one_chip):
    tq, td = _tiles("nn_distance")
    _compile(functools.partial(nn_distance.nn_sq_dists, n_coords=2,
                               tq=tq, td=td), one_chip,
             ((N_PTS, W), jnp.float32), ((N_PTS, W), jnp.float32),
             ((N_PTS,), jnp.bool_))


def test_bound_matrices_compiles(one_chip):
    tn, tm = _tiles("bound_matrices")
    _compile(functools.partial(bound_matrix.bound_matrices, n_coords=2,
                               tn=tn, tm=tm), one_chip,
             ((DRAIN, W), jnp.float32), ((DRAIN,), jnp.float32),
             ((SLOTS, W), jnp.float32), ((SLOTS,), jnp.float32))


@pytest.mark.parametrize("max_level", range(len(LEVELS)))
def test_bound_grid_compiles_in_scoped_vmem(one_chip, max_level):
    """N=15 (max_level 3) once needed 18.7 MiB of scoped VMEM (limit
    16 MiB); levels past 16 nodes (max_level >= 5) reduce in a rolled
    loop."""
    tb, ts = _tiles("bound_grid")
    levels = LEVELS[:max_level + 1]
    n = levels[-1][1]
    _compile(functools.partial(bound_matrix.bound_grid, levels=levels,
                               n_coords=2, tb=tb, ts=ts), one_chip,
             ((DRAIN, n, W), jnp.float32), ((DRAIN, n), jnp.float32),
             ((DRAIN, n), jnp.bool_), ((SLOTS, n, W), jnp.float32),
             ((SLOTS, n), jnp.float32), ((SLOTS, n), jnp.bool_))


def test_intersect_counts_compiles(one_chip):
    ta, tb = _tiles("set_intersect")
    _compile(functools.partial(set_intersect.intersect_counts, ta=ta, tb=tb),
             one_chip, ((DRAIN, SIG_WORDS), jnp.uint32),
             ((SLOTS, SIG_WORDS), jnp.uint32))
