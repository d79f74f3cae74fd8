"""Planner (meshplan) decisions: layouts, optimizers, accumulation."""
from repro.configs import SHAPES, ShapeSpec, get_config
from repro.core.meshplan import plan_job
from repro.core.profiles import Profile


def test_kimi_train_uses_adafactor_and_zero3():
    p = plan_job(get_config("kimi-k2-1t-a32b"), SHAPES["train_4k"])
    assert p.optimizer == "adafactor"          # AdamW fp32 > fleet HBM
    assert p.moe_impl == "ep_a2a"
    assert p.rules.fsdp is not None
    assert p.rules.batch == ("data", "model")  # ZeRO-3 DP layout


def test_moonshot_keeps_adamw_with_fsdp():
    p = plan_job(get_config("moonshot-v1-16b-a3b"), SHAPES["train_4k"])
    assert p.optimizer == "adamw"
    assert p.moe_impl == "ep"
    assert p.rules.fsdp is not None            # 27.7B opt states need ZeRO


def test_small_dense_is_network_profile():
    p = plan_job(get_config("qwen2-0.5b"), SHAPES["train_4k"])
    assert p.profile == Profile.NETWORK
    assert p.optimizer == "adamw"


def test_decode_profile_is_memory():
    p = plan_job(get_config("llama3.2-1b"), SHAPES["decode_32k"])
    assert p.profile == Profile.MEMORY


def test_long_context_batch1_uses_cache_sequence_sharding():
    p = plan_job(get_config("rwkv6-3b"), SHAPES["long_500k"])
    assert p.rules.batch is None
    assert p.rules.cache_seq is not None


def test_optimized_network_profile_goes_coarse():
    base = plan_job(get_config("qwen2-0.5b"), SHAPES["train_4k"])
    opt = plan_job(get_config("qwen2-0.5b"), SHAPES["train_4k"],
                   optimized=True)
    assert base.rules.vocab == "model"         # paper-faithful TP baseline
    assert opt.rules.vocab is None             # coarse DP layout
    assert opt.rules.batch == ("data", "model")
    assert opt.accum_steps == 1


def test_optimized_ssm_gets_zero1():
    opt = plan_job(get_config("rwkv6-3b"), SHAPES["train_4k"],
                   optimized=True)
    assert opt.rules.opt_fsdp is not None
    assert opt.rules.fsdp is None              # params stay replicated


def test_accumulation_bounds_remat_carry():
    p = plan_job(get_config("internvl2-26b"), SHAPES["train_4k"])
    assert p.accum_steps >= 8                  # 48L x d6144 carry


def test_policy_none_disables_optimization():
    opt = plan_job(get_config("qwen2-0.5b"), SHAPES["train_4k"],
                   optimized=True, policy="none")
    assert opt.rules.vocab == "model"          # stays at baseline layout


def test_one_chip_train_plan_bounds_loss_chunk():
    """On one chip the whole batch is local: the CE chunk shrinks until one
    chunk's f32 logits fit 1 GiB (8 x 128 x 153600 x 4 B), and chunking
    applies because the chunk is shorter than the sequence."""
    cfg = get_config("qwen2-0.5b")
    p = plan_job(cfg, ShapeSpec("cli", "train", 512, 8), n_chips=1)
    assert p.remat and p.accum_steps == 1
    assert p.ce_chunk == 128
    assert 8 * p.ce_chunk * cfg.padded_vocab * 4 <= 2 ** 30
