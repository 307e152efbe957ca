"""Compile rehearsals for a TPU v5e that is described, not attached.

The TPU compiler is installed with libtpu, so a CPU process can compile
the serve path's programs for a ``v5e:2x2`` topology and see what the
chip's compiler refuses: unaligned slices, too much fast memory, a
program that does not fit one chip.  Nothing runs, so these say nothing
about results or times.  The topology is described inside a fixture —
never at import — so collecting the file loads no libtpu; a process
that shares the machine with another libtpu user needs
``ALLOW_MULTIPLE_LIBTPU_LOAD=1``.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.registry import get_config
from repro.kernels.paged_attention import paged_attention
from repro.models import model as M
from repro.models import transformer as tf

GIB = 1 << 30
V5E_HBM = 16 * GIB
ARCH = "minitron-4b"


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# (B, Sq): decode, speculative verify (spec_k 4) and one chunked-prefill
# bucket, over a pool of 16-row blocks holding 512 positions per slot
@pytest.mark.parametrize("B,Sq", [(4, 1), (4, 5), (1, 32)],
                         ids=["decode", "verify", "chunk"])
def test_paged_attention_compiles_for_v5e(one_chip, B, Sq):
    cfg = get_config(ARCH)
    K, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    R = cfg.num_heads // K
    bs, nb = 16, 512 // 16
    NB = B * nb
    args = (_sds((B, Sq, K, R, hd), jnp.bfloat16, one_chip),
            _sds((NB, bs, K, hd), jnp.bfloat16, one_chip),
            _sds((NB, bs, K, hd), jnp.bfloat16, one_chip),
            _sds((B, nb), jnp.int32, one_chip),
            _sds((B,), jnp.int32, one_chip),
            _sds((B,), jnp.int32, one_chip))
    compiled = paged_attention.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


# both cells' real kernel shapes (bench/cells): slots, table entries of
# 16 rows, kv heads, query heads per kv head, query rows per slot
@pytest.mark.parametrize("B,nb,K,R,Sq", [(14, 256, 4, 8, 1),
                                         (18, 64, 8, 3, 1),
                                         (1, 256, 4, 8, 256)],
                         ids=["yi_decode", "chat_decode", "yi_chunk"])
def test_paged_attention_compiles_at_cell_shapes(one_chip, B, nb, K, R, Sq):
    """The tile width the kernel picks at the cells' shapes fits the
    chip's VMEM: an overflow fails here, off the chip."""
    hd, bs = 128, 16
    NB = B * nb
    args = (_sds((B, Sq, K, R, hd), jnp.bfloat16, one_chip),
            _sds((NB, bs, K, hd), jnp.bfloat16, one_chip),
            _sds((NB, bs, K, hd), jnp.bfloat16, one_chip),
            _sds((B, nb), jnp.int32, one_chip),
            _sds((B,), jnp.int32, one_chip),
            _sds((B,), jnp.int32, one_chip))
    compiled = paged_attention.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_full_width_decode_step_holds_kernel(one_chip):
    """One full-width decode step through the compiled kernel: the
    program holds the Pallas call and its arguments fit one chip."""
    cfg = get_config(ARCH)
    B, bs, nb = 4, 16, 128 // 16
    params = _on(one_chip, M.param_specs(cfg))
    cache = _on(one_chip, jax.eval_shape(
        lambda: tf.init_paged_cache(cfg, B * nb, bs)))
    step = jax.jit(lambda p, c, t, i, tb: tf.decode_step(
        p, c, t, i, cfg, tables=tb, kernels="compiled"))
    compiled = step.lower(params, cache,
                          _sds((B, 1), jnp.int32, one_chip),
                          _sds((B,), jnp.int32, one_chip),
                          _sds((B, nb), jnp.int32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().argument_size_in_bytes < V5E_HBM


def test_full_width_init_fits_one_chip(one_chip):
    """The entry points' jitted init: the bf16 weights are its output,
    and no f32 draw of a whole stacked leaf sits beside them."""
    cfg = get_config(ARCH)
    compiled = M.init_params_jit.lower(
        _sds((2,), jnp.uint32, one_chip), cfg).compile()
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes + mem.temp_size_in_bytes < V5E_HBM
    assert mem.temp_size_in_bytes < GIB
