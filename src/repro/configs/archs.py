"""The 10 assigned architectures, exact dims from the assignment table.

Pattern factorizations (scan unit × n_units + tail) are chosen so the unit is
the smallest repeating structure:

  recurrentgemma-2b : (rglru, rglru, local) × 8 + (rglru, rglru)   = 26
  gemma3-1b         : (local×5, attn) × 4 + (local, local)         = 26
  all-attention LMs : (attn,) × n_layers
  rwkv6-3b          : (rwkv,) × 32
"""
from repro.configs.base import ArchConfig, EncoderSpec, MoESpec, register

RECURRENTGEMMA_2B = register(ArchConfig(
    name="recurrentgemma-2b", family="hybrid",
    n_layers=26, d_model=2560, n_heads=10, n_kv_heads=1, head_dim=256,
    d_ff=7680, vocab=256_000,
    pattern_unit=("rglru", "rglru", "local"), n_units=8,
    tail=("rglru", "rglru"),
    local_window=2048, rope_theta=10_000.0,
    ffn_kind="geglu", norm_type="rms", tied_embeddings=True,
    embed_scale=True, final_softcap=30.0,
    rnn_width=2560, conv_width=4,
    source="arXiv:2402.19427; hf",
))

GEMMA3_1B = register(ArchConfig(
    name="gemma3-1b", family="dense",
    n_layers=26, d_model=1152, n_heads=4, n_kv_heads=1, head_dim=256,
    d_ff=6912, vocab=262_144,
    pattern_unit=("local", "local", "local", "local", "local", "attn"),
    n_units=4, tail=("local", "local"),
    local_window=512, rope_theta=1_000_000.0, rope_theta_local=10_000.0,
    qk_norm=True, ffn_kind="geglu", norm_type="rms",
    tied_embeddings=True, embed_scale=True,
    source="hf:google/gemma-3-1b-pt; unverified",
))

SMOLLM_360M = register(ArchConfig(
    name="smollm-360m", family="dense",
    n_layers=32, d_model=960, n_heads=15, n_kv_heads=5, head_dim=64,
    d_ff=2560, vocab=49_152,
    pattern_unit=("attn",), n_units=32,
    rope_theta=10_000.0, ffn_kind="swiglu", tied_embeddings=True,
    source="hf:HuggingFaceTB/SmolLM-135M; hf",
))

LLAMA32_1B = register(ArchConfig(
    name="llama3.2-1b", family="dense",
    n_layers=16, d_model=2048, n_heads=32, n_kv_heads=8, head_dim=64,
    d_ff=8192, vocab=128_256,
    pattern_unit=("attn",), n_units=16,
    rope_theta=500_000.0, ffn_kind="swiglu", tied_embeddings=True,
    source="hf:meta-llama/Llama-3.2-1B; unverified",
))

QWEN2_05B = register(ArchConfig(
    name="qwen2-0.5b", family="dense",
    n_layers=24, d_model=896, n_heads=14, n_kv_heads=2, head_dim=64,
    d_ff=4864, vocab=151_936,
    pattern_unit=("attn",), n_units=24,
    rope_theta=1_000_000.0, qkv_bias=True, ffn_kind="swiglu",
    tied_embeddings=True,
    source="arXiv:2407.10671; hf",
))

RWKV6_3B = register(ArchConfig(
    name="rwkv6-3b", family="ssm",
    n_layers=32, d_model=2560, n_heads=40, n_kv_heads=40, head_dim=64,
    d_ff=8960, vocab=65_536,
    pattern_unit=("rwkv",), n_units=32,
    norm_type="layer", norm_eps=1e-5, embed_norm=True,
    tied_embeddings=False,
    mix_lora_rank=32, decay_lora_rank=64, head_size_divisor=8,
    source="arXiv:2404.05892 (Eagle and Finch), Section 4; "
           "hf:RWKV/v6-Finch-3B-HF config.json; LoRA ranks from RWKV-LM "
           "RWKV_Tmix_x060",
))

KIMI_K2 = register(ArchConfig(
    name="kimi-k2-1t-a32b", family="moe",
    n_layers=61, d_model=7168, n_heads=64, n_kv_heads=8, head_dim=112,
    d_ff=2048, vocab=163_840,
    pattern_unit=("attn",), n_units=61,
    rope_theta=50_000.0, ffn_kind="swiglu", tied_embeddings=False,
    moe=MoESpec(n_experts=384, top_k=8, d_ff_expert=2048),
    source="arXiv:2501.kimi2; unverified (paper-table)",
))

MOONSHOT_16B = register(ArchConfig(
    name="moonshot-v1-16b-a3b", family="moe",
    n_layers=48, d_model=2048, n_heads=16, n_kv_heads=16, head_dim=128,
    d_ff=1408, vocab=163_840,
    pattern_unit=("attn",), n_units=48,
    rope_theta=50_000.0, ffn_kind="swiglu", tied_embeddings=True,
    moe=MoESpec(n_experts=64, top_k=6, d_ff_expert=1408),
    source="hf:moonshotai/Moonlight-16B-A3B; hf",
))

INTERNVL2_26B = register(ArchConfig(
    name="internvl2-26b", family="vlm",
    n_layers=48, d_model=6144, n_heads=48, n_kv_heads=8, head_dim=128,
    d_ff=16384, vocab=92_553,
    pattern_unit=("attn",), n_units=48,
    rope_theta=1_000_000.0, ffn_kind="swiglu", tied_embeddings=False,
    n_media_tokens=256,                      # stubbed InternViT patch embeds
    source="arXiv:2404.16821; hf",
))

WHISPER_SMALL = register(ArchConfig(
    name="whisper-small", family="audio",
    n_layers=12, d_model=768, n_heads=12, n_kv_heads=12, head_dim=64,
    d_ff=3072, vocab=51_865,
    pattern_unit=("attn",), n_units=12,
    use_rope=False, ffn_kind="gelu", norm_type="layer", tied_embeddings=True,
    encoder=EncoderSpec(n_layers=12, n_ctx=1500, d_model=768, n_heads=12,
                        d_ff=3072),
    source="arXiv:2212.04356; unverified",
))

ALL = [RECURRENTGEMMA_2B, GEMMA3_1B, SMOLLM_360M, LLAMA32_1B, QWEN2_05B,
       RWKV6_3B, KIMI_K2, MOONSHOT_16B, INTERNVL2_26B, WHISPER_SMALL]
