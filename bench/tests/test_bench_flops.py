"""The FLOP counters against counts made by hand for qwen2-0.5b."""
import json
import pathlib

from bench import flops

QWEN = json.loads((pathlib.Path(__file__).resolve().parents[1] / "configs"
                   / "qwen2-0.5b.json").read_text())

# one layer's matmuls per token, by hand: q 896*14*64 = 802,816;
# k and v 2*896*2*64 = 229,376; o 802,816; MLP 3*896*4864 = 13,074,432;
# sum 14,909,440 multiply-adds = 29,818,880 FLOPs, x 24 layers
LAYERS = 715_653_120
HEAD = 272_269_312            # 2 * 896 * 151,936
ATTN_PER_POS = 86_016         # 24 layers * 2 * 2 * 14 heads * 64


def test_decode_token():
    assert flops.decode(QWEN, 100) == LAYERS + 100 * ATTN_PER_POS + HEAD \
        == 996_524_032


def test_prefill_counts_causal_attention_and_one_head():
    # positions 1..128 seen by the 128 prompt tokens: 128 * 129 / 2
    assert flops.prefill(QWEN, 128) == 128 * LAYERS \
        + ATTN_PER_POS * 8256 + HEAD == 92_586_016_768


def test_window_counts_decode_of_admitted_and_population_alike():
    from bench.runners import serve

    class Rec:
        counters = {}
        spans = {}
        requests = [
            # admitted in the window: prefill, its token, then 2 decoded
            {"answered": True, "due": 0.0, "prompt_len": 128,
             "n_tokens": 3, "decoded": 2, "late_s": 0.0},
            # population: prefilled before the window, 2 decoded in it
            {"answered": True, "due": None, "prompt_len": 128,
             "n_tokens": 2, "decoded": 2, "late_s": None},
            {"answered": False, "due": 1.0, "prompt_len": 64,
             "n_tokens": 0, "decoded": 0, "late_s": 0.5}]

    class NoCompiles:
        n = 0

    rec = Rec()
    serve._count(rec, QWEN, {}, NoCompiles())
    assert rec.flops["prefill"] == flops.prefill(QWEN, 128)
    # each decoded token sees the prompt, the prefill's token, and those
    # decoded before it: 129 and 130 positions, twice
    assert rec.flops["decode"] == 2 * (flops.decode(QWEN, 129)
                                       + flops.decode(QWEN, 130))
    assert rec.counters["admitted"] == 1
    assert rec.counters["decoded_tokens"] == 4


def test_recurrent_model_has_no_attention_term():
    c = {"family": "rwkv6", "num_hidden_layers": 1, "hidden_size": 64,
         "num_attention_heads": 2, "intermediate_size": 128,
         "decay_lora_rank": 4, "vocab_size": 10}
    # time-mix 5*64*64 + 2*64*4, channel-mix 2*64*128 + 64*64, WKV 2*2*2*32*32
    per = 2 * (5 * 4096 + 512 + 16384 + 4096) + 8192
    assert flops.decode(c, 1000) == flops.decode(c, 1) == per + 2 * 64 * 10
    assert flops.prefill(c, 7) == 7 * per + 2 * 64 * 10
