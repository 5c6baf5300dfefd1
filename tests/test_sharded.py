"""Distribution correctness on a multi-device host mesh.

Runs in a subprocess with XLA_FLAGS=--xla_force_host_platform_device_count=8
so the main pytest process keeps seeing 1 device (required: smoke tests and
benches must not inherit the fake-device setting)."""
import json
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P
from repro.configs import reduced_config
from repro.parallel import sharding as S
from repro.parallel.compression import compressed_psum
from repro.train.optimizer import AdamW, constant_lr
from repro.train.step import make_train_step
from repro.train.train_state import TrainState, init_state

assert len(jax.devices()) == 8
cfg = reduced_config("gemma2-9b")
opt = AdamW(schedule=constant_lr(1e-3))
step = make_train_step(cfg, opt, accum_steps=2)
toks = jax.random.randint(jax.random.key(1), (8, 32), 0, cfg.vocab_size)
batch = {"tokens": toks, "labels": toks}

# single-device reference
state0 = init_state(jax.random.key(0), cfg, opt)
_, m_ref = jax.jit(step)(state0, batch)

# 4x2 (data, model) mesh
mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(AxisType.Auto, AxisType.Auto))
S.set_active_mesh(mesh)
state = init_state(jax.random.key(0), cfg, opt)
pshard = S.param_shardings(cfg, state.params, mesh)
repl = NamedSharding(mesh, P())
sshard = TrainState(step=repl, params=pshard,
                    opt_state=type(state.opt_state)(count=repl, mu=pshard, nu=pshard))
state = jax.device_put(state, sshard)
dshard = {k: NamedSharding(mesh, S.data_specs(mesh, v.shape)) for k, v in batch.items()}
batch_s = jax.device_put(batch, dshard)
with mesh:
    state2, m = jax.jit(step, in_shardings=(sshard, dshard),
                        out_shardings=(sshard, None))(state, batch_s)

# sharded == unsharded (same math, different layout)
ok_loss = abs(float(m["loss"]) - float(m_ref["loss"])) < 5e-3

# shard_map compressed gradient psum across the data axis
from jax import shard_map
g_local = jnp.arange(32, dtype=jnp.float32).reshape(4, 8) * 0.01
def sync(g):
    summed, resid = compressed_psum({"g": g}, "data", codec="int8")
    return summed["g"], resid["g"]
f = shard_map(sync, mesh=mesh, in_specs=P("data", None),
              out_specs=(P("data", None), P("data", None)))
summed, resid = f(g_local)
true = np.tile(np.asarray(g_local).reshape(4, 1, 8).sum(0), (4, 1))
err = np.abs(np.asarray(summed) - true).max()
ok_comp = err < 0.05

print(json.dumps({"ok_loss": ok_loss, "loss": float(m["loss"]),
                  "loss_ref": float(m_ref["loss"]), "ok_comp": bool(ok_comp),
                  "comp_err": float(err)}))
"""


@pytest.mark.slow
def test_sharded_train_step_and_compressed_psum():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["ok_loss"], res
    assert res["ok_comp"], res


_PAGED_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
# pin the fused Pallas page-walk kernel: the decode cell must lower it
# under SPMD, not silently fall back to the gather path
os.environ["REPRO_DECODE_ATTN"] = "fused"
import json
import jax
from jax.sharding import AxisType, PartitionSpec as P
from repro.configs import reduced_config
from repro.models.config import ShapeCfg
from repro.models.layers import PagedKVCache
from repro.launch import specs as SP
from repro.parallel.sharding import set_active_mesh

mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(AxisType.Auto, AxisType.Auto))
set_active_mesh(mesh)
cfg = reduced_config("qwen2.5-14b")
shape = ShapeCfg("decode_paged_smoke", 256, 8, "decode")
step_fn, args, in_sh, out_sh = SP.input_specs(cfg, shape, mesh,
                                              kv_layout="paged",
                                              page_size=64)
# the pool's page dim must shard over the data axis; block table replicated
pools = [s for s in jax.tree.leaves(
             in_sh[2], is_leaf=lambda x: isinstance(x, PagedKVCache))
         if isinstance(s, PagedKVCache)]
assert pools, "decode cell lowered without a paged leaf"
k_spec = pools[0].k.spec
bt_spec = pools[0].block_table.spec
# JAX >= 0.8 normalizes a 1-tuple axis entry to the bare name
ok_pages = k_spec[1] in ("data", ("data",)) and bt_spec == P(None, None, None)
with mesh:
    jitted = jax.jit(step_fn, in_shardings=in_sh, out_shardings=out_sh,
                     donate_argnums=(2,))
    compiled = jitted.lower(*args).compile()
print(json.dumps({"ok_pages": bool(ok_pages), "k_spec": str(k_spec),
                  "n_devices": int(mesh.devices.size),
                  "hlo_chars": len(compiled.as_text())}))
"""


@pytest.mark.slow
def test_paged_decode_cell_lowers_on_mesh():
    """The paged decode cell (global page pool sharded over `data`, KV
    heads over `model`, replicated block table) must lower and compile on
    a multi-device host mesh — the serving analogue of the dry-run. Runs
    with REPRO_DECODE_ATTN=fused pinned, so the fused Pallas page-walk
    kernel itself must partition (batch over `data`, KV heads over
    `model`), not just the jnp gather fallback."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", _PAGED_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["ok_pages"], res
    assert res["hlo_chars"] > 0


_HANDOFF_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
from repro.configs import reduced_config
from repro.models import model as M
from repro.models.layers import KVCache, PagedKVCache
from repro.parallel import sharding as S
from repro.serve.engine import ServeEngine
from repro.train.step import make_prefill_step

mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(AxisType.Auto, AxisType.Auto))
cfg = reduced_config("qwen2.5-14b")
psz = 8
pool_pages = 12            # % data size (4) == 0: the page dim must shard
B, T = 2, 64
cache = M.init_cache(cfg, B, T, dtype=jnp.float32, paged=(pool_pages, psz))

# the staged fragment: a real batch-1 prefill run (what the prefill pool
# hands off at a two-pool completion); 19 tokens -> 3 pages
plen = 19
cap = -(-plen // psz) * psz
params = M.init_params(jax.random.key(0), cfg)
toks = jax.random.randint(jax.random.key(1), (1, plen), 0, cfg.vocab_size,
                          dtype=jnp.int32)
_, frag = jax.jit(make_prefill_step(cfg))(
    params, toks, M.init_cache(cfg, 1, cap, dtype=jnp.float32))
row = np.full((T // psz,), -1, np.int32)
row[:3] = [2, 3, 4]
row = jnp.asarray(row)
slot = jnp.asarray(1, jnp.int32)
keep = jnp.asarray(0, jnp.int32)

# unsharded reference: the unified engine's fused in-place insert
ref = jax.jit(ServeEngine._insert_impl)(cache, frag, slot, row, keep)

# pool sharding: page dim over the data axis, block table replicated
cspecs = S.cache_specs(cfg, cache, mesh, B)
pool = [s for s in jax.tree.leaves(
            cspecs, is_leaf=lambda x: isinstance(x, PagedKVCache))
        if isinstance(s, PagedKVCache)][0]
ok_pool = (pool.k[1] in ("data", ("data",))
           and pool.block_table == P(None, None, None))

# fragment sharding: token dim REPLICATED over data (whole-page handoff —
# each data shard keeps its local pages at the scatter), heads over model
fspecs = S.handoff_frag_specs(cfg, frag, mesh)
kv = [s for s in jax.tree.leaves(
          fspecs, is_leaf=lambda x: isinstance(x, KVCache))
      if isinstance(s, KVCache)][0]
ok_frag = ("data" not in jax.tree.leaves(tuple(kv.k))
           and "model" in jax.tree.leaves(tuple(kv.k)))

# reshard_handoff is layout-only: bit-identical content
frag_s = S.reshard_handoff(frag, mesh, cfg)
ok_reshard = all(np.array_equal(np.asarray(a), np.asarray(b))
                 for a, b in zip(jax.tree.leaves(frag),
                                 jax.tree.leaves(frag_s)))

# the same insert under SPMD on the sharded pool + resharded fragment
cache_s = jax.device_put(cache, jax.tree.map(
    lambda s: NamedSharding(mesh, s), cspecs))
with mesh:
    out = jax.jit(ServeEngine._insert_impl)(cache_s, frag_s, slot, row, keep)
ok_equal = all(np.array_equal(np.asarray(a), np.asarray(b))
               for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(out)))

bt = np.asarray(jax.tree.leaves(
    out, is_leaf=lambda x: isinstance(x, PagedKVCache))[0].block_table)
ok_bind = list(bt[0, 1, :3]) == [2, 3, 4]

print(json.dumps({"ok_pool": bool(ok_pool), "ok_frag": bool(ok_frag),
                  "ok_reshard": bool(ok_reshard), "ok_equal": bool(ok_equal),
                  "ok_bind": bool(ok_bind), "k_spec": str(pool.k),
                  "frag_k_spec": str(kv.k)}))
"""


@pytest.mark.slow
def test_handoff_reshard_bitidentical_on_mesh():
    """Two-pool KV-page handoff under SPMD (DESIGN.md §10): on a 4x2
    (data, model) mesh the pool's page dim shards over `data` while the
    staged fragment keeps its token dim replicated (handoff_frag_specs —
    whole pages land on whichever shard owns them), `reshard_handoff` is
    a pure layout move, and the scatter+bind splice produces a pool
    bit-identical to the unsharded unified insert."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", _HANDOFF_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    for key in ("ok_pool", "ok_frag", "ok_reshard", "ok_equal", "ok_bind"):
        assert res[key], res
