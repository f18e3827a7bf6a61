"""The main path's Pallas kernels compile for a described TPU v5e.

No chip is needed: the TPU compiler is installed and compiles for a chip
that is described and not attached.  Interpret-mode tests never check the
(8, 128) tiling rule or VMEM limits; these compiles do.  Each test asserts
the kernel reached the compiled program as a ``tpu_custom_call``.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.fl.engine import bucket_size
from repro.kernels.flash_attention import flash_attention_bhld
from repro.kernels.stale_aggregate import stale_aggregate_flat
from repro.models import build_model


def _flat_size(model_name: str) -> int:
    shapes = jax.eval_shape(build_model(get_config(model_name)).init,
                            jax.random.PRNGKey(0))
    return sum(x.size for x in jax.tree.leaves(shapes))


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler can be loaded here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    cc.reset_cache()


def _compiled_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


@pytest.mark.parametrize("cohorts,n,dtype", [
    (bucket_size(5), "mnist_dnn", jnp.float32),   # engine round, Table I A=5
    (4, 1_000_000, jnp.bfloat16),                 # SPMD cohorts
])
def test_stale_aggregate_compiles_for_v5e(one_chip, cohorts, n, dtype):
    if isinstance(n, str):
        n = _flat_size(n)

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    text = _compiled_text(
        lambda p, b, m: stale_aggregate_flat(p, b, m, beta=0.07,
                                             interpret=False),
        sds((n,), dtype), sds((cohorts, n), dtype),
        sds((cohorts,), jnp.float32))
    assert "tpu_custom_call" in text


def test_flash_attention_compiles_for_v5e_at_yi_6b_widths(one_chip):
    # yi_6b: 32 query heads, 4 kv heads, head_dim 128, 2048 tokens
    q = jax.ShapeDtypeStruct((1, 32, 2048, 128), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 4, 2048, 128), jnp.bfloat16,
                              sharding=one_chip)
    text = _compiled_text(
        lambda q, k, v: flash_attention_bhld(q, k, v, interpret=False),
        q, kv, kv)
    assert "tpu_custom_call" in text
