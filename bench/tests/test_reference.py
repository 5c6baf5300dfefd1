"""The plain reference against the program's own forward, tiny, on CPU.

The program keeps its residual stream in bfloat16 and rounds every matrix
product's inputs to bfloat16; the reference is float32 throughout. So the
two agree to bfloat16's rounding, amplified by the layers, except at the
few rows where that rounding flips a top-k routing choice."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import weights as W
from bench.reference import plain
from bench.tests import tiny


@pytest.mark.parametrize("name", ["granite-moe-3b", "phi3-medium-10l"])
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


@pytest.mark.parametrize("name", ["granite-moe-3b", "phi3-medium-10l"])
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
