"""The family files (bench/families/): each configuration finds its
family by `model_type`; the plain reference against the program's own
forward; the counts against hand counts and pinned values; the weights
pinned bit for bit.

The program keeps its residual stream in bfloat16 and rounds every matrix
product's inputs to bfloat16; the reference is float32 throughout. So the
two agree to bfloat16's rounding, amplified by the layers, except at the
few rows where that rounding flips a top-k routing choice."""
import dataclasses
import hashlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import families
from bench import flops as F
from bench import weights as W
from bench.reference import plain
from bench.tests import tiny

NAMES = ["granite-moe-3b", "phi3-medium-10l"]
GRANITE = W.sizes(W.load_config("granite-moe-3b"))
PHI3 = W.sizes(W.load_config("phi3-medium-10l"))


def _digest(params) -> str:
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(params):
        h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("path", sorted((W.ROOT / "configs").glob("*.json")),
                         ids=lambda p: p.stem)
def test_every_configuration_resolves_to_a_family(path):
    conf = json.loads(path.read_text())
    fam = families.family(conf["model_type"])
    assert all(callable(getattr(fam, f)) for f in families.PROVIDES)
    s = W.sizes(conf)
    assert s["model_type"] == conf["model_type"]
    assert {"V", "Vp", "window"} <= set(s)


def test_an_unknown_model_type_fails_before_any_weight():
    conf = dict(W.load_config("phi3-medium-10l"), model_type="nosuchmodel")
    with pytest.raises(ValueError, match="nosuchmodel") as e:
        W.make_weights(conf, 1)
    for name in families.names():
        assert repr(name) in str(e.value)
    assert "granitemoe" in families.names() and "phi3" in families.names()
    assert not any(n.startswith("_") for n in families.names())


@pytest.mark.parametrize("name", NAMES)
def test_reference_matches_program_forward(name):
    from repro.models import model as M

    conf = tiny.conf(name)
    sz = W.sizes(conf)
    cfg = dataclasses.replace(W.arch_config(conf, "tiny"), moe_dropless=True)
    params = W.make_weights(conf, 2**35 + 1, jnp.float32)
    toks = np.random.default_rng(0).integers(0, sz["V"], 64).astype(np.int32)
    got = np.asarray(jax.jit(lambda p, t: M.forward(p, cfg, t)[0])(
        params, jnp.asarray(toks)[None])[0])[:, :sz["V"]]
    ref = np.asarray(plain.logits(params, jnp.asarray(toks),
                                  s=plain.static_sizes(sz), block=32))
    assert np.isinf(ref[:, sz["V"]:]).all()
    ref = ref[:, :sz["V"]]
    rows = np.max(np.abs(got - ref), axis=1) / np.max(np.abs(ref))
    assert np.median(rows) < 2e-2, np.median(rows)
    assert np.mean(rows < 5e-2) >= 0.9, rows
    assert np.mean(got.argmax(1) == ref.argmax(1)) >= 0.9


@pytest.mark.parametrize("name", NAMES)
def test_fp8_control_departs_further_than_the_program(name):
    from repro.models import model as M

    conf = tiny.conf(name)
    sz = W.sizes(conf)
    key = plain.static_sizes(sz)
    cfg = dataclasses.replace(W.arch_config(conf, "tiny"), moe_dropless=True)
    params = W.make_weights(conf, 17, jnp.float32)
    toks = jnp.asarray(np.random.default_rng(1).integers(0, sz["V"], 64),
                       jnp.int32)
    got = np.asarray(jax.jit(lambda p, t: M.forward(p, cfg, t)[0])(
        params, toks[None])[0])[:, :sz["V"]]
    ref = np.asarray(plain.logits(params, toks, s=key, block=32))[:, :sz["V"]]
    ctl = np.asarray(plain.logits(params, toks, s=key, fp8=True,
                                  block=32))[:, :sz["V"]]
    err = lambda x: np.median(np.max(np.abs(x - ref), axis=1))
    assert err(ctl) > 3 * err(got)


# the counts and weights as they stood before the families were split
# out, at the published sizes and for tiny.conf at seed 2**35 + 1
PINS = {
    "granite-moe-3b": ((2712171193344, 2168202240, 268435456, 412518187008,
                        553648128), "222146d217b132f5"),
    "phi3-medium-10l": ((10711060316160, 7563509760, 209715200,
                         429706444800, 550502400), "106ff5866ca8f9ee"),
}


@pytest.mark.parametrize("name", NAMES)
def test_counts_are_pinned(name):
    s = W.sizes(W.load_config(name))
    assert (F.prefill_flops(s, 1536), F.decode_flops(s, 2047),
            F.decode_kv_bytes(s, 2047, 4),
            F.attention_flops(s, F.causal_pairs(2048)),
            F.prefill_attention_bytes(s, 1536)) == PINS[name][0]


@pytest.mark.parametrize("name", NAMES)
def test_weights_are_pinned(name):
    params = W.make_weights(tiny.conf(name), 2**35 + 1, jnp.float32)
    assert _digest(params).startswith(PINS[name][1])


def test_a_named_draw_reaches_its_leaf_only(monkeypatch):
    """A family that names `uniform(2, 3)` for the final norm's gain gets
    it there, and every other leaf keeps its bits."""
    conf = tiny.conf("granite-moe-3b")
    fam = families.family(conf["model_type"])
    before = W.make_weights(conf, 5, jnp.float32)
    tree = fam.tree

    def named(s):
        t = tree(s)
        t["final_norm"]["w"] = dataclasses.replace(t["final_norm"]["w"],
                                                   draw=W.uniform(2.0, 3.0))
        return t

    monkeypatch.setattr(fam, "tree", named)
    after = W.make_weights(conf, 5, jnp.float32)
    g = np.asarray(after["final_norm"]["w"])
    assert ((g >= 2.0) & (g < 3.0)).all() and np.ptp(g) > 0
    before["final_norm"]["w"] = after["final_norm"]["w"]
    assert _digest(before) == _digest(after)


def test_granite_decode_step_by_hand():
    # per layer: q 1536·1536, k and v 1536·512 each, o 1536·1536,
    # router 1536·40, 8 of 40 experts × 3 matrices of 1536·512
    per_layer = (1536 * 1536 + 2 * 1536 * 512 + 1536 * 1536 + 1536 * 40
                 + 8 * 3 * 1536 * 512)
    assert per_layer == 25_227_264
    weights = 32 * per_layer + 1536 * 49155
    ctx = 999                       # the token at position 999 sees 1000
    attn = 32 * 4 * 24 * 64 * 1000
    assert F.decode_flops(GRANITE, ctx) == 2 * weights + attn
    # K and V of 1000 positions, 8 heads of 64, 32 layers, fp32
    assert F.decode_kv_bytes(GRANITE, ctx, 4) == 32 * 2 * 8 * 64 * 4 * 1000


def test_phi3_prefill_by_hand():
    per_layer = (5120 * 5120 + 2 * 5120 * 1280 + 5120 * 5120
                 + 3 * 5120 * 17920)
    assert per_layer == 340_787_200
    T = 2048
    pairs = T * (T + 1) // 2
    want = (2 * T * 10 * per_layer + 2 * 5120 * 32064
            + 10 * 4 * 40 * 128 * pairs)
    assert F.prefill_flops(PHI3, T) == want
    assert F.attention_flops(PHI3, F.causal_pairs(T)) == (
        10 * 4 * 40 * 128 * pairs)
    # q, k, v in bf16 and the fp32 output, once each
    assert F.prefill_attention_bytes(PHI3, T) == 10 * T * 128 * (
        (40 + 20) * 2 + 40 * 4)


def test_granite_prefill_counts_top_k_experts_only():
    T = 512
    dense_all = F.prefill_flops(dict(GRANITE, k=40), T)
    top8 = F.prefill_flops(GRANITE, T)
    expert_share = 2 * T * 32 * 3 * 1536 * 512
    assert dense_all - top8 == (40 - 8) * expert_share


def test_peaks_are_keyed_by_device_kind():
    assert F.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        F.peaks("TPU v9000")
