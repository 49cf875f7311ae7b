"""Ahead-of-time TPU v5e compiles of the main-path Pallas kernels.

Each kernel is lowered and compiled for a described (not attached) v5e
chip at the widths the chip runs use: llama3.2-1b paged decode (H=32,
KV=8, hd=64, 16 slots, 16-token blocks over a 64k-token pool), a
2048-token flash prefill, and ``mrd_combine`` over a 32 MiB gradient
bucket.  This catches what interpret mode cannot: tiling-misaligned
slices, VMEM overflow, layouts Mosaic refuses.  Nothing runs, so no
result or time is checked here.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU compiler library, so the
worker that runs this file loads it and the others never try.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention.kernel import flash_attention_fwd
from repro.kernels.flash_attention.paged_kernel import paged_attention_fwd
from repro.kernels.mrd_combine.kernel import QBLOCK, mrd_combine_fwd


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler library, or it is held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for the described chip cannot be read back from the
    # persistent cache without the chip, so keep it out of the cache
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_paged_attention_compiles_at_llama3_2_1b_decode(one_chip):
    S, H, KV, hd, bs = 16, 32, 8, 64, 16
    N = 4097  # 64k tokens + the trash block
    nb = 64
    assert (N - 1) * bs >= 65536
    bf = jnp.bfloat16
    compiled = _compile(
        paged_attention_fwd, one_chip,
        ((S, H, hd), bf), ((N, bs, KV, hd), bf), ((N, bs, KV, hd), bf),
        ((S, nb), jnp.int32), ((S,), jnp.int32),
    )
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes >= 2 * N * bs * KV * hd * 2


def test_flash_attention_compiles_at_2048_prefill(one_chip):
    bf = jnp.bfloat16
    _compile(
        lambda q, k, v: flash_attention_fwd(q, k, v, causal=True), one_chip,
        ((1, 2048, 32, 64), bf), ((1, 2048, 8, 64), bf), ((1, 2048, 8, 64), bf),
    )


def test_mrd_combine_compiles_on_32mib_bucket(one_chip):
    n = 32 * 2**20 // 4
    _compile(
        mrd_combine_fwd, one_chip,
        ((n,), jnp.float32), ((n,), jnp.int8), ((n // QBLOCK,), jnp.float32),
    )
