"""The Pallas kernels compile for a TPU v5e described, not attached.

Interpret mode (every other kernel test) cannot see Mosaic's block-shape
and VMEM rules; these compiles can. Each test lowers the chip's program —
`precision.EXACT_CPU_CONTAINERS` off (bf16 containers) and
`interpret=False` — against one device of a described `v5e:2x2` topology,
at the serving shapes of granite-moe-3b-a800m, and checks that the Mosaic
kernel is in the compiled module. The topology is described inside a
fixture (never at import): only one process may load libtpu, and pytest
workers import every test file.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.core import precision
from repro.kernels import autotune
from repro.kernels.sa_attention import sa_attention
from repro.kernels.sa_decode_attention import sa_paged_decode_attention
from repro.kernels.sa_matmul import sa_matmul_pallas

# granite-moe-3b-a800m: 24 query / 8 KV heads of 64, d_model 1536
H, KVH, HD, D_MODEL = 24, 8, 64, 1536


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip compile written to the persistent cache cannot be
    # read back without the chip: keep the cache out of these compiles
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture
def one_chip(topo, monkeypatch):
    from jax.sharding import SingleDeviceSharding

    monkeypatch.setattr(precision, "EXACT_CPU_CONTAINERS", False)
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


@pytest.mark.parametrize("fmt", ["bf16", "fp32"])
def test_paged_decode_compiles_at_granite_decode_shape(one_chip, fmt):
    """Batch 8 slots, page 16, 128 pages per slot (max_seq_len 2048), an
    fp32 page pool, the autotune default grid snapped to a legal one."""
    B, psz, P = 8, 16, 128
    n_pages = B * P + 1
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    pol = precision.PrecisionPolicy(input_format=fmt)
    text = _compiled_text(
        lambda *a: sa_paged_decode_attention(*a, policy=pol,
                                             interpret=False),
        s((B, 1, H, HD), jnp.float32), s((n_pages, psz, KVH, HD), jnp.float32),
        s((n_pages, psz, KVH, HD), jnp.float32), s((n_pages, psz), jnp.int32),
        s((B, P), jnp.int32), s((B,), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("T", [512, 600, 1000])
def test_flash_attention_compiles_at_prompt_length(one_chip, T):
    """Prefill prompt lengths that are and are not multiples of the
    kernel's 512-row block or of 128; bf16 SA-contract inputs."""
    s = lambda shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                                           sharding=one_chip)
    text = _compiled_text(
        lambda q, k, v: sa_attention(q, k, v, interpret=False),
        s((1, H, T, HD)), s((1, KVH, T, HD)), s((1, KVH, T, HD)))
    assert "tpu_custom_call" in text


def test_sa_matmul_compiles_at_full_width(one_chip):
    """A prefill-sized GEMM against granite's q projection width with the
    autotune heuristic's blocks."""
    m, k, n = 2048, D_MODEL, H * HD
    bm, bn, bk = autotune.default_blocks(m, n, k)
    s = lambda shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                                           sharding=one_chip)
    text = _compiled_text(
        lambda a, w: sa_matmul_pallas(a, w, bm=bm, bn=bn, bk=bk,
                                      interpret=False),
        s((m, k)), s((k, n)))
    assert "tpu_custom_call" in text
