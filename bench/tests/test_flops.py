"""bench/flops.py against counts made by hand from the published sizes."""
from bench import flops as F
from bench import weights as W

GRANITE = W.sizes(W.load_config("granite-moe-3b"))
PHI3 = W.sizes(W.load_config("phi3-medium-10l"))


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
    try:
        F.peaks("TPU v9000")
    except KeyError:
        pass
    else:
        raise AssertionError("an unknown device must be an error")
