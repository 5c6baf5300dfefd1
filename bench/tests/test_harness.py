"""A whole run of a cell, tiny and on CPU, past the harness's look for a
chip: it comes out correct, and not correct with the timed path broken
(a token altered where the engine produces it), and the float8 control,
judged in the program's place, is not correct."""
import json

import pytest

from bench import cell
from bench.tests import tiny

SPEC = json.loads((cell.ROOT.parent / "BENCHMARK.json").read_text())
# tiny-size limit: sound runs read 0 here (bf16 rounding never flips a
# greedy token at d_model 64); a wrong token reads O(1)
LIMIT = 0.05


def run(name, seed=2**33 + 9, control=False):
    w = {c["name"]: c for c in SPEC["workloads"]}[name]
    mine = [m for m in SPEC["end_to_end"]
            if name in m.get("workloads", [name])]
    return cell.run(tiny.conf(w["config"]), tiny.mix(w["traffic"], LIMIT),
                    name="tiny", seed=seed, seconds=4.0, trace=False,
                    e2e=mine, per_layer=[], peaks=tiny.PEAKS, control=control)


@pytest.mark.parametrize("name", ["granite-chat", "phi3-10l-longdoc"])
def test_sound_run_is_correct(name):
    out = run(name)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert len(out["metrics"]) >= 2
    assert out["device"]["count"] == 1


@pytest.mark.parametrize("name", ["granite-chat", "phi3-10l-longdoc"])
def test_altered_token_is_not_correct(name, monkeypatch):
    from repro.serve.engine import ServeEngine

    chunk_fn = ServeEngine._chunk_fn

    def altered(self, *a, **k):
        fn = chunk_fn(self, *a, **k)

        def run_chunk(*args):
            out = fn(*args)
            toks = out[4]
            # every slot's token at step 1 of each chunk, +1
            return (*out[:4], toks.at[1].set((toks[1] + 1)
                                             % self.cfg.vocab_size))
        return run_chunk

    monkeypatch.setattr(ServeEngine, "_chunk_fn", altered)
    out = run(name)
    assert not out["correct"], out["checks"]


def test_control_is_not_correct():
    """The float8 control, in the program's place, fails the limit that
    the program's own tokens meet in the same run."""
    out = run("granite-chat", control=True)
    assert out["program_gap"] <= LIMIT
    assert not out["correct"], out["checks"]
    assert out["checks"]["served_gap"]["value"] > LIMIT


def test_kv_bytes_reads_the_pages_beside_recurrent_state():
    """A hybrid stack's pool holds fp32 SSM state beside its KV pages;
    the K and V pages alone give the bytes of a value."""
    import jax.numpy as jnp

    from repro.configs import reduced_config
    from repro.serve.engine import ServeEngine

    from bench import serve as S

    engine = ServeEngine(reduced_config("hymba-1.5b"), None, batch=2,
                         cache_len=64, eos_id=-1, kv_layout="paged",
                         cache_dtype=jnp.bfloat16)
    assert S.kv_bytes(engine) == 2
