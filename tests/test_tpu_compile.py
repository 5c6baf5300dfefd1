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
import re

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


def _while_body_instructions(text):
    """The instruction lines of every computation a while body reaches
    (the body itself, the fusions and calls inside it, nested loops)."""
    comps, name = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) .*\{$", line)
        if head:
            name = head.group(1)
            comps[name] = []
        elif line == "}":
            name = None
        elif name is not None:
            comps[name].append(line)
    callee = r"(?:body|condition|calls|to_apply)=%([\w.\-]+)"
    todo = [c for lines in comps.values() for line in lines
            for c in re.findall(r"body=%([\w.\-]+)", line)]
    seen = set()
    while todo:
        c = todo.pop()
        if c not in seen and c in comps:
            seen.add(c)
            todo += [d for line in comps[c] for d in re.findall(callee, line)]
    return [line for c in seen for line in comps[c]]


def _phi3_10l_serving(one_chip, monkeypatch):
    """phi3-medium-10l's serving shapes in the `phi3-10l-longdoc` cell: the
    config, bf16 weights and the fp32 page pool (8 slots, 1,025 pages of
    16, max_seq_len 2048), placed on the described chip; Mosaic kernels."""
    import dataclasses

    from repro.configs import get_config
    from repro.kernels import ops
    from repro.models import model as M

    monkeypatch.setattr(ops, "INTERPRET", False)
    cfg = dataclasses.replace(get_config("phi3-medium-14b"), num_layers=10,
                              vocab_size=32064)
    place = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip)
    params = jax.tree.map(place, M.abstract_params(cfg, jnp.bfloat16))
    pool = jax.tree.map(place, M.init_cache(
        cfg, 8, 2048, dtype=jnp.float32, abstract=True, paged=(1025, 16)))
    return cfg, params, pool, place


def _pool_shaped(lines, k, ops):
    """Names of the ops in `lines` that output K's whole stacked shape."""
    dims = ",".join(map(str, k.shape))
    return [line.split(" = ")[0].strip() for line in lines
            if re.search(rf"= f32\[{dims}\]\S* ({ops})\(", line)]


def test_decode_chunk_updates_the_pool_in_place(one_chip, monkeypatch):
    """The 8-step decode chunk at phi3-medium-10l's serving shape, pool
    donated: no op inside the step or layer loops rebuilds the stacked K
    or V pool (a `copy` or `dynamic-update-slice` of its whole shape) —
    the layer scan carries the pool and scatters each step's rows into it
    — and the chunk's temporaries stay below two stacked K+V pools (a
    restacked copy per step would add at least one more)."""
    from jax import lax

    from repro.train.step import make_serve_step

    cfg, params, pool, place = _phi3_10l_serving(one_chip, monkeypatch)
    B = pool[0]["kv"].block_table.shape[1]
    vec = place(jax.ShapeDtypeStruct((B,), jnp.int32))
    serve_step = make_serve_step(cfg)

    def chunk(params, tok, cache, pos):
        def body(carry, _):
            tok, cache, pos = carry
            logits, cache = serve_step(params, tok[:, None], cache, pos)
            nxt = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
            return (nxt, cache, pos + 1), nxt

        return lax.scan(body, (tok, cache, pos), length=8)

    compiled = jax.jit(chunk, donate_argnums=(2,)).lower(
        params, vec, pool, vec).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    k = pool[0]["kv"].k
    assert _pool_shaped(_while_body_instructions(text), k,
                        "copy|dynamic-update-slice") == []
    kv_pool_bytes = 2 * k.size * jnp.dtype(k.dtype).itemsize
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * kv_pool_bytes


def test_admission_scatters_into_the_pool_in_place(one_chip, monkeypatch):
    """`_insert` of a 2048-token prefill fragment into the donated pool at
    the same shape writes whole pages where the pool lies: no op copies
    the stacked pool (a flattened page·offset view needs a relayout of the
    whole pool, in and out) and the program needs no temporaries of its
    size."""
    from repro.models import model as M
    from repro.serve.engine import ServeEngine

    cfg, _, pool, place = _phi3_10l_serving(one_chip, monkeypatch)
    frag = jax.tree.map(place, M.init_cache(cfg, 1, 2048, dtype=jnp.float32,
                                            abstract=True))
    i32 = lambda *shape: place(jax.ShapeDtypeStruct(shape, jnp.int32))
    compiled = jax.jit(ServeEngine._insert_impl, donate_argnums=(0,)).lower(
        pool, frag, i32(), i32(pool[0]["kv"].block_table.shape[2]),
        i32()).compile()
    k = pool[0]["kv"].k
    assert _pool_shaped(compiled.as_text().splitlines(), k, "copy") == []
    layer_bytes = k.size // k.shape[0] * jnp.dtype(k.dtype).itemsize
    assert compiled.memory_analysis().temp_size_in_bytes < layer_bytes


@pytest.mark.parametrize("program", ["decode_chunk", "prefill_8192"])
def test_granite_h_longdoc_fits_one_chip(one_chip, monkeypatch, program):
    """The `granite-h-10l-longdoc` cell's programs at its serving shapes:
    granite-4.0-h-small cut to one period of ten layers and 9 of 72 experts,
    bf16 weights, 32 slots over an fp32 pool of 17,409 pages of 16
    (max_seq_len 8704: every slot at its longest), with the recurrent state
    of nine Mamba2 layers beside it. The 8-step decode chunk (pool donated)
    and the largest prefill bucket (8192 tokens, the flash kernel on, as on
    the chip) each fit below 15 GB of the chip's 16 GiB together with what
    stays on the device while they run: weights and pool (decode: 8.35 GB
    of arguments and 0.09 GB of temporaries; prefill: 4.94 + 5.55 GB
    beside the 3.52 GB pool)."""
    import dataclasses

    from jax import lax

    from repro.configs import get_config
    from repro.core import optflags
    from repro.kernels import ops
    from repro.models import model as M
    from repro.train.step import make_bucketed_prefill_step, make_serve_step

    monkeypatch.setattr(ops, "INTERPRET", False)
    monkeypatch.setitem(optflags.FLAGS, "pallas_attention", True)
    cfg = dataclasses.replace(get_config("granite-4.0-h-small"),
                              num_layers=10, experts_held=(0, 9))
    place = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip)
    params = jax.tree.map(place, M.abstract_params(cfg, jnp.bfloat16))
    pool = jax.tree.map(place, M.init_cache(
        cfg, 32, 8704, dtype=jnp.float32, abstract=True, paged=(17409, 16)))
    nbytes = lambda tree: sum(a.size * jnp.dtype(a.dtype).itemsize
                              for a in jax.tree.leaves(tree))
    i32 = lambda *shape: place(jax.ShapeDtypeStruct(shape, jnp.int32))
    if program == "decode_chunk":
        serve_step = make_serve_step(cfg)

        def chunk(params, tok, cache, pos):
            def body(carry, _):
                tok, cache, pos = carry
                logits, cache = serve_step(params, tok[:, None], cache, pos)
                nxt = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
                return (nxt, cache, pos + 1), nxt

            return lax.scan(body, (tok, cache, pos), length=8)

        compiled = jax.jit(chunk, donate_argnums=(2,)).lower(
            params, i32(32), pool, i32(32)).compile()
        beside = 0                       # weights and pool are arguments
    else:
        frag = jax.tree.map(place, M.init_cache(
            cfg, 1, 8192, dtype=jnp.float32, abstract=True))
        compiled = jax.jit(make_bucketed_prefill_step(cfg),
                           donate_argnums=(2,)).lower(
            params, i32(1, 8192), frag, i32(), i32()).compile()
        beside = nbytes(pool)
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes + beside
    assert used < 15e9, used
