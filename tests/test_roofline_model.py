"""The roofline's HBM traffic model (`benchmarks/roofline.py`) reads each
layer's mixer: pure attention, pure Mamba2 and Hymba's parallel heads
keep their pinned bytes, and granite-4.0-h's interleaved stack counts KV
only in its attention layers and recurrent state only in its Mamba2
layers."""
import importlib.util
from pathlib import Path

import pytest

from repro.configs import get_config

_spec = importlib.util.spec_from_file_location(
    "roofline", Path(__file__).parents[1] / "benchmarks" / "roofline.py")
roofline = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(roofline)

N_DEV = 16
PINNED = [
    ("granite-moe-3b-a800m", "decode_32k", 17316179968.0),
    ("granite-moe-3b-a800m", "long_500k", 2268188672.0),
    ("granite-moe-3b-a800m", "prefill_32k", 6694129102848.0),
    ("granite-moe-3b-a800m", "train_4k", 4420262621184.0),
    ("hymba-1.5b", "decode_32k", 1626493440.0),
    ("hymba-1.5b", "long_500k", 294766080.0),
    ("hymba-1.5b", "prefill_32k", 6972815987200.0),
    ("hymba-1.5b", "train_4k", 4600442483200.0),
    ("mamba2-2.7b", "decode_32k", 3089408000.0),
    ("mamba2-2.7b", "long_500k", 374005760.0),
    ("mamba2-2.7b", "prefill_32k", 322475171840.0),
    ("mamba2-2.7b", "train_4k", 972362260480.0),
    ("qwen2.5-14b", "decode_32k", 53466427392.0),
    ("qwen2.5-14b", "long_500k", 8291241984.0),
    ("qwen2.5-14b", "prefill_32k", 16977706414080.0),
    ("qwen2.5-14b", "train_4k", 11788891975680.0),
]


@pytest.mark.parametrize("arch,shape,want", PINNED)
def test_bytes_pinned(arch, shape, want):
    assert roofline.analytic_bytes_per_device(arch, shape, N_DEV) == want


def test_interleaved_stack_counts_each_mixer():
    cfg = get_config("granite-4.0-h-small")
    shape = roofline.SHAPES_BY_NAME["decode_32k"]
    n_attn = sum(cfg.mixer(i) == "attention" for i in range(cfg.num_layers))
    n_ssm = sum(cfg.mixer(i) == "mamba" for i in range(cfg.num_layers))
    assert (n_attn, n_ssm) == (4, 36)
    GB, T, L = shape.global_batch, shape.seq_len, cfg.num_layers
    kv = n_attn * 2 * GB * T * cfg.num_kv_heads * cfg.hd * 2.0
    state = (GB * cfg.n_ssm_heads * cfg.ssm_head_dim * cfg.ssm_state
             * 4.0 * 2 * n_ssm)
    rest = (2.0 * cfg.active_param_count() / N_DEV
            + roofline.ACT_PASSES["decode"] * L * (GB / N_DEV)
            * cfg.d_model * 2.0)
    got = roofline.analytic_bytes_per_device("granite-4.0-h-small",
                                             "decode_32k", N_DEV)
    assert got == pytest.approx(rest + (kv + state) / N_DEV, rel=1e-12)
