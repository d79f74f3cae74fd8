"""Granularity decisions -> concrete JAX mesh + sharding rules.

This is where the paper's application-layer planner output binds to the
infrastructure layer for *real* jobs: the same Algorithm-1 decision that the
cluster simulator uses ("how finely to split, where the pieces may go") is
expressed on a TPU mesh as *which logical axes are partitioned and over which
mesh axes* — the TPU analogue of "how many containers and which nodes".

Profile -> layout policy (defaults; §Perf iterates on these):

* collective-bound ("network"): keep collectives in the fastest domain —
  tensor-parallel axes confined to the intra-pod ``model`` axis, batch over
  (pod, data); never shard params across pods.  Coarse analogue: if the
  model fits one chip, drop TP entirely (params replicated, pure DP).
* compute-bound ("cpu"): fine granularity is free — TP over ``model``,
  DP over (pod, data): the paper's one-task-per-container operating point.
* HBM-bound ("memory"): spread state — FSDP param sharding over the data
  axes on top of TP (balanced groups are what keeps this straggler-free).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax

from repro.configs import ArchConfig, ShapeSpec
from repro.core.profiles import Profile
from repro.models.sharding import Rules


@dataclasses.dataclass(frozen=True)
class JobPlan:
    arch: str
    shape: str
    profile: Profile
    rules: Rules
    moe_impl: str           # dense | ep | ep_a2a
    optimizer: str          # adamw | adafactor
    remat: bool
    ce_chunk: int
    accum_steps: int = 1    # microbatch gradient accumulation
    notes: str = ""


# HBM napkin model (v5e: 16 GiB/chip) used to pick param layouts before the
# first compile; the compiled program's own memory analysis is the ground
# truth.
HBM_PER_CHIP = 16 * 2 ** 30


def _param_bytes(cfg: ArchConfig, optimizer: str) -> int:
    n = cfg.param_count()
    per = 2                                   # bf16 params
    per += 2                                  # grads (bf16)
    per += 12 if optimizer == "adamw" else 1  # m+v+master vs factored
    return n * per


def default_profile(cfg: ArchConfig, shape: ShapeSpec) -> Profile:
    """Heuristic profile from the shape's kind and the model's size."""
    if shape.kind == "decode":
        return Profile.MEMORY                 # decode reads params+cache/token
    # training/prefill: small dense models on many chips are collective-bound
    if cfg.param_count() < 2e9 and cfg.moe is None:
        return Profile.NETWORK
    return Profile.CPU


def plan_job(cfg: ArchConfig, shape: ShapeSpec, n_chips: int = 256,
             profile: Optional[Profile] = None,
             policy: str = "granularity",
             optimized: bool = False) -> JobPlan:
    """``optimized=False`` is the paper-faithful baseline (one-size TP
    layout).  ``optimized=True`` applies Algorithm 1 to the *measured*
    profile with the layouts validated in EXPERIMENTS.md §Perf:

    * network/memory-profile dense trains -> coarse per-shard granularity
      (pure 256-way DP, no TP resharding)  [qwen2: 17x step time]
    * attention-free (ssm) trains -> DP + ZeRO-1 opt-state sharding
      [rwkv6: 18x]
    * 1T-class MoE -> hierarchical + int8 ZeRO-3 weight gathers
      [kimi multi-pod: 2.9x]
    """
    profile = profile or default_profile(cfg, shape)
    notes = []

    # optimizer choice: AdamW unless the fleet cannot hold fp32 states
    optimizer = "adamw"
    if shape.kind == "train" and \
            _param_bytes(cfg, "adamw") > 0.5 * HBM_PER_CHIP * 512:
        optimizer = "adafactor"
        notes.append("adamw fp32 states exceed fleet HBM -> adafactor")

    # MoE layout: EP over `model`; ZeRO-3 the weights when they exceed HBM
    moe_impl = "dense"
    rules = Rules()
    if cfg.moe is not None:
        moe_impl = "ep"
        resident = cfg.param_count() * 2 / 16      # bf16, experts/model axis
        if resident > 0.55 * HBM_PER_CHIP and shape.kind != "decode":
            # 1T-class: pure ZeRO-3 data parallelism for the dense params,
            # tokens sharded over (data x model), experts dispatched with
            # all_to_all over the model axis (DeepSeek-style EP)
            moe_impl = "ep_a2a"
            if shape.kind == "train":
                rules = Rules(batch=("data", "model"), seq="pod",
                              vocab=None, heads=None, kv_heads=None,
                              ffn=None, expert="model",
                              fsdp=("pod", "data"))
            else:  # prefill: batch over data, sequence over model
                rules = Rules(batch=("data",), seq="model", vocab=None,
                              heads=None, kv_heads=None, ffn=None,
                              expert="model", fsdp=("pod", "data"))
            notes.append("1T-class MoE: ZeRO-3 DP + token sharding over "
                         "(data,model), expert all_to_all over model")

    # params (+grads +opt states) too big for 16-way TP? ZeRO-3 over the
    # data axes (manual JIT gathers inside the MoE shard_map; GSPMD auto-
    # gathers for the dense params)
    state_mult = 4 + (8 if optimizer == "adamw" else 1)
    if rules.fsdp is None and \
            cfg.param_count() * state_mult / 16 > 0.6 * HBM_PER_CHIP:
        rules = dataclasses.replace(rules, fsdp=("pod", "data"))
        notes.append("params+grads+opt per chip exceed HBM headroom under "
                     "16-way TP -> ZeRO-3/FSDP over the data axes")

    # decode shapes with batch too small for the batch axes: shard the
    # KV-cache sequence dim instead (sequence parallelism for decode)
    if shape.kind == "decode":
        batch_ways = 32 if n_chips > 256 else 16
        if shape.global_batch < batch_ways:
            rules = dataclasses.replace(rules, batch=None,
                                        cache_seq=("pod", "data"))
            notes.append("batch < data ways -> KV-cache sequence sharding")

    # the paper's coarse rule for collective-bound jobs: drop TP when the
    # whole model state fits a single chip comfortably
    if profile == Profile.NETWORK and shape.kind == "train" and \
            _param_bytes(cfg, optimizer) < 0.25 * HBM_PER_CHIP \
            and policy != "none":
        notes.append("collective-bound + fits on chip: coarse candidate "
                     "(kept TP for baseline; see §Perf)")

    # microbatch accumulation: bound the per-device remat carry
    # (L_units x tokens_micro x d_model x 2B, x3 for f32 recurrent states)
    accum = 1
    ce_chunk = 0
    if shape.kind == "train":
        batch_ways = 32 if n_chips > 256 else 16
        if rules.batch == ("data", "model"):
            batch_ways = 256
        batch_ways = min(batch_ways, n_chips)
        tokens_loc = shape.global_batch * shape.seq_len / batch_ways
        fam_mult = 3 if cfg.family in ("ssm", "hybrid") else 1
        carry = (cfg.stack_n_layers * tokens_loc * cfg.d_model * 2
                 * fam_mult)
        target = 2 * 2 ** 30
        while accum < shape.global_batch // batch_ways and \
                carry / accum > target:
            accum *= 2
        if accum > 1:
            notes.append(f"remat carry {carry/2**30:.0f}GiB -> "
                         f"{accum}x grad accumulation")
        # sequence-chunked CE: bound one chunk's f32 logits per device
        rows = max(1.0, shape.global_batch / batch_ways / accum)
        ce_chunk = 1024
        while ce_chunk > 128 and \
                rows * ce_chunk * cfg.padded_vocab * 4 > 2 ** 30:
            ce_chunk //= 2

    if optimized and shape.kind == "train" and policy != "none":
        if cfg.moe is None and cfg.family in ("dense", "vlm", "audio") \
                and profile == Profile.NETWORK:
            rules = Rules(batch=("data", "model"), vocab=None, heads=None,
                          kv_heads=None, ffn=None, expert=None, rnn=None)
            accum = 1
            notes.append("OPT: network profile -> coarse per-shard "
                         "granularity (pure DP over data x model)")
        elif cfg.family == "ssm":
            rules = Rules(batch=("data", "model"), vocab=None, heads=None,
                          kv_heads=None, ffn=None, expert=None, rnn=None,
                          opt_fsdp=("data", "model"))
            accum = 1
            notes.append("OPT: attention-free -> DP + ZeRO-1 opt state")

    return JobPlan(arch=cfg.name, shape=shape.name, profile=profile,
                   rules=rules, moe_impl=moe_impl, optimizer=optimizer,
                   remat=(shape.kind == "train"), ce_chunk=ce_chunk,
                   accum_steps=accum, notes="; ".join(notes))
