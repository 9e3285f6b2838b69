"""Compile-only checks of the main-path Pallas kernels for a TPU v5e chip.

The TPU compiler is installed even where no chip is attached: it compiles
for a *described* v5e chip, and refuses what the chip's compiler would
refuse (unlowerable primitives, unaligned blocks, kernels that overflow
VMEM). Nothing runs, so these tests say nothing about results or times —
the interpret-mode tests in ``test_kernels.py`` cover results.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and under several test
workers only the worker that runs this file loads it.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.fused_topk import (quant_lb2_pallas, topk_l2_masked_pallas,
                                      topk_l2_pallas)
from repro.kernels.lpgf_force import lpgf_force_pallas
from repro.kernels.pairwise_l2 import pairwise_sq_l2_pallas

G, C = 64, 4096          # one engine round: 64 queries x 64 tiles of 64 rows


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("d", [128, 768])
@pytest.mark.parametrize("k", [10, 100])
@pytest.mark.parametrize("with_lb", [False, True])
def test_topk_l2_masked_compiles(one_chip, d, k, with_lb):
    shapes = [((G, d), jnp.float32), ((G, C, d), jnp.float32),
              ((G, C), jnp.bool_)]
    if with_lb:
        shapes.append(((G, C), jnp.float32))

        def fn(q, p, v, lb2):
            return topk_l2_masked_pallas(q, p, v, k, lb2=lb2)
    else:
        def fn(q, p, v):
            return topk_l2_masked_pallas(q, p, v, k)
    _compile(fn, one_chip, *shapes)


@pytest.mark.parametrize("d", [128, 768])
@pytest.mark.parametrize("k", [2, 10, 100])
def test_topk_l2_compiles(one_chip, d, k):
    _compile(lambda q, p: topk_l2_pallas(q, p, k), one_chip,
             ((4096, d), jnp.float32), ((65536, d), jnp.float32))


@pytest.mark.parametrize("d", [128, 768])
@pytest.mark.parametrize("precision", ["int8", "bf16"])
def test_quant_lb2_compiles(one_chip, d, precision):
    cdt = jnp.int8 if precision == "int8" else jnp.bfloat16
    _compile(lambda *a: quant_lb2_pallas(*a, precision=precision), one_chip,
             ((G, d), jnp.float32), ((G, C, d), cdt),
             ((G, C), jnp.float32), ((G, C), jnp.float32),
             ((G, C), jnp.float32), ((G, C), jnp.bool_))


@pytest.mark.parametrize("d", [128, 768])
def test_pairwise_sq_l2_compiles(one_chip, d):
    _compile(pairwise_sq_l2_pallas, one_chip,
             ((256, d), jnp.float32), ((16384, d), jnp.float32))


def test_lpgf_force_compiles(one_chip):
    _compile(lambda x: lpgf_force_pallas(x, 7.5, 1.0), one_chip,
             ((8192, 128), jnp.float32))
