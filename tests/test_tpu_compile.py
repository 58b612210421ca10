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
N = 1_000_000    # SIFT1M payload rows, device-resident


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
                                _spec(one_chip, (N, D)),
                                _spec(one_chip, (ARENA,), jnp.int32),
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
                                _spec(one_chip, (N, 8), jnp.uint8),
                                _spec(one_chip, (ARENA,), jnp.int32),
                                interpret=False))


@pytest.mark.parametrize("kind,width,dtype,hlo", [
    ("flat", D, jnp.float32, "f32"), ("pq", 8, jnp.uint8, "u8")])
def test_arena_gather_compiles(one_chip, kind, width, dtype, hlo):
    """The scorer gathers a 2^19-row arena from the device-resident
    payload inside its own program: exactly one gather fusion makes the
    ``(2^19, width)`` arena, which the kernel then reads.  The re-score's
    rows come with the device select, one gather of ``QB *
    SHORTLIST_ROWS`` rows, or, after a host cut, in one pull."""
    from repro.ann.scan import (SHORTLIST_ROWS, _adc_scorers,
                                _device_selector, _flat_scorers, _row_puller)

    u_pad = 1 << 19
    lhs = (QB, D) if kind == "flat" else (QB, width, 256)
    scorer = (_flat_scorers if kind == "flat" else _adc_scorers)()["pallas"]
    text = scorer.lower(_spec(one_chip, lhs),
                        _spec(one_chip, (N, width), dtype),
                        _spec(one_chip, (u_pad,), jnp.int32),
                        interpret=False).compile().as_text()
    assert "tpu_custom_call" in text and "while(" not in text

    def gathers(text, shape):
        pat = re.compile(rf"^\s*(ROOT )?%\S+ = {hlo}\[{shape}\]\S* fusion\("
                         r'.*kind=kCustom.*op_name="[^"]*gather"')
        return [ln for ln in text.splitlines() if pat.match(ln)]

    assert len(gathers(text, f"{u_pad},{width}")) == 1
    pull = _row_puller().lower(_spec(one_chip, (N, width), dtype),
                               _spec(one_chip, (2048,), jnp.int32))
    assert len(gathers(pull.compile().as_text(), f"2048,{width}")) == 1
    sel = _device_selector().lower(
        _spec(one_chip, (QB, u_pad)), _spec(one_chip, (QB, 16), jnp.int32),
        _spec(one_chip, (1024,), jnp.int32),
        _spec(one_chip, (1024,), jnp.int32),
        _spec(one_chip, (N, width), dtype),
        _spec(one_chip, (u_pad,), jnp.int32),
        c_pad=32768, k=64, engine="pallas", interpret=False)
    text = sel.compile().as_text()
    assert "while(" not in text
    assert len(gathers(text, f"{QB * SHORTLIST_ROWS},{width}")) == 1


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
                             _spec(one_chip, (N, D)),
                             _spec(one_chip, (ARENA,), jnp.int32),
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
        _spec(one_chip, (N, D)),
        _spec(one_chip, (1 << 19,), jnp.int32),
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
