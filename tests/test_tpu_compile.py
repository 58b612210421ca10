"""Ahead-of-time compiles of the serving path's Pallas kernels for TPU v5e.

Interpret mode (every other kernel test) cannot see what the TPU compiler
refuses: block shapes that break its tiling rules, bool loop carries,
layouts XLA and Mosaic disagree on.  These tests lower and compile each
kernel of the IVF, PQ, graph and device-select paths for a described (not
attached) v5e chip at SIFT1M widths (d=128, query block 64) and check that
the compiled program really contains the kernel (``tpu_custom_call``).

The topology is described only inside the module fixture — never at
import time — so that under a multi-worker run only the worker that runs
this file loads the TPU compiler library.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

D = 128          # SIFT1M width
QB = 64          # scan engines' default query block
ARENA = 16384    # IVF arena rows (power-of-two bucket)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # the TPU compiler library otherwise writes its logs under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # compiles for a described chip are written to the persistent cache but
    # cannot be read back without one; keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler installed
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(lowered):
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text


def test_l2_dist_ivf_blocks_compile(one_chip):
    from repro.ann.scan import _flat_scorers

    scorer = _flat_scorers()["pallas"]
    _assert_kernel(scorer.lower(_spec(one_chip, (QB, D)),
                                _spec(one_chip, (ARENA, D)),
                                interpret=False))


def test_l2_dist_graph_blocks_compile(one_chip):
    from repro.ann.graph_scan import _graph_scorers

    scorer = _graph_scorers()["pallas_vec"]
    _assert_kernel(scorer.lower(_spec(one_chip, (QB, D)),
                                _spec(one_chip, (100_000, D)),
                                _spec(one_chip, (1024,), jnp.int32),
                                _spec(one_chip, (1024,), jnp.int32),
                                interpret=False))


def test_l2_top1_compiles(one_chip):
    from repro.kernels.l2_topk import l2_top1

    _assert_kernel(l2_top1.lower(_spec(one_chip, (10_000, D)),
                                 _spec(one_chip, (1024, D)),
                                 interpret=False))


def test_pq_adc_vmapped_compiles(one_chip):
    from repro.ann.scan import _adc_scorers

    scorer = _adc_scorers()["pallas"]
    _assert_kernel(scorer.lower(_spec(one_chip, (QB, 8, 256)),
                                _spec(one_chip, (ARENA, 8), jnp.uint8),
                                interpret=False))


@pytest.mark.parametrize("n,k", [(ARENA, 32), (1000, 10)])
def test_seg_topk_compiles(one_chip, n, k):
    from repro.kernels.seg_topk import seg_topk

    _assert_kernel(seg_topk.lower(_spec(one_chip, (QB, n)),
                                  _spec(one_chip, (QB,), jnp.int32),
                                  k=k, interpret=False))


def test_device_selector_compiles(one_chip):
    from repro.ann.scan import _device_selector

    run = _device_selector()
    _assert_kernel(run.lower(_spec(one_chip, (QB, ARENA)),
                             _spec(one_chip, (QB, 16), jnp.int32),
                             _spec(one_chip, (1024,), jnp.int32),
                             _spec(one_chip, (1024,), jnp.int32),
                             c_pad=ARENA, k=32, engine="pallas",
                             interpret=False))


def test_device_selector_map_has_no_loop(one_chip):
    """The candidate->arena map compiles to dense vector work: no ``while``
    (a search loop) and one gather fusion over the ``(qb, c_pad)`` block,
    the f32 distance gather from the scored block."""
    from repro.ann.scan import _device_selector

    c_pad = 32768
    text = _device_selector().lower(
        _spec(one_chip, (QB, 1 << 19)),
        _spec(one_chip, (QB, 16), jnp.int32),
        _spec(one_chip, (1024,), jnp.int32),
        _spec(one_chip, (1024,), jnp.int32),
        c_pad=c_pad, k=32, engine="pallas", interpret=False,
    ).compile().as_text()
    assert "while(" not in text
    gather = re.compile(rf"^\s*%\S+ = (\w+)\[{QB * c_pad}\]\S* fusion\("
                        r'.*kind=kCustom.*op_name="[^"]*gather"')
    dtypes = [m.group(1) for line in text.splitlines()
              if (m := gather.match(line))]
    assert dtypes == ["f32"]


def test_flat_select_runner_compiles(one_chip):
    from repro.ann.scan import _flat_select_runner

    run = _flat_select_runner()
    _assert_kernel(run.lower(_spec(one_chip, (QB, D)),
                             _spec(one_chip, (ARENA, D)),
                             k=32, engine="pallas", interpret=False,
                             nvalid=ARENA - 7))
