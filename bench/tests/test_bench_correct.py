"""``correct`` at a size a test run holds, on the CPU: the harness's look
for a chip is skipped and the rest of a run is driven, once as it is, once
with the float8 control in the program's place, and once per fault that a
serve cell can have, planted in the timed path.  All but the first must
come out not correct.

The limit here is set for this size from its own readings (the chip
cells' limit, in ``bench/configs``, is set from chip readings at the
published widths): the program's widest logit gap read 0.0008 to 0.0046
on seeds 1-4 and the float8 control's 0.038 to 0.082, so 0.02.
"""
import time

import jax
import pytest

from bench import harness, traffic
from bench.control import float8_control
from bench.runners import serve

SERVE_CELL = "qwen2-0.5b.serve-chat"


def shrink_serve(cfg, c, tr):
    from repro.configs import scaled_down
    small = scaled_down(cfg, vocab=2048)    # a multiple of 2048: no padding
    c = dict(c, hidden_size=small.d_model, intermediate_size=small.d_ff,
             num_hidden_layers=small.n_layers,
             num_attention_heads=small.n_heads,
             num_key_value_heads=small.n_kv_heads, head_dim=small.head_dim,
             vocab_size=small.vocab, reference_block=16)
    c["check"] = dict(c["check"], serve_logit_gap=0.02)
    tr = dict(tr, slots=4, cache_len=64,
              prompt_len={"dist": "exponential", "mean": 10, "min": 1,
                          "max": 16, "buckets": [8, 16]},
              output_len={"dist": "exponential", "mean": 6, "min": 1,
                          "max": 16},
              arrival={"process": "poisson", "rate_per_s": 20.0},
              population=2, trace_seconds=1)
    return small, c, tr


def _serve(seed=2, fault=None):
    cell = harness.find_cell(SERVE_CELL)
    rec = harness.Record(cell=cell, seed=seed, seconds=2.0, trace=False,
                         peak={"bf16_flops": 1.0})
    serve.run(rec, jax.devices(), t0=time.perf_counter(),
              shrink=shrink_serve, fault=fault, log=lambda s: None)
    return rec


def test_serve_sound_run_is_correct():
    rec = _serve()
    assert rec.correct(), rec.checks
    assert rec.info["sampled_tokens"] >= 40
    pop = [r for r in rec.requests if r["due"] is None]
    assert len(pop) == 2 and all(r["decoded"] for r in pop)
    assert all(r["uid"] >= traffic.POPULATION_UID0 for r in pop)
    occ = rec.occupancy
    assert occ["slots"] == 4 and occ["cache_len"] == 64
    assert 0 < occ["live_slots_mean"] <= 4


def test_serve_control_is_not_correct():
    with float8_control():
        rec = _serve()
    assert all(harness._within(c)
               for c in rec.info["program_checks"].values())
    assert not rec.correct(), rec.checks
    assert rec.checks["logit_gap"]["value"] > rec.checks["logit_gap"]["limit"]
    assert serve.judge.__name__ == "judge" and \
        serve.judge.__module__ == serve.__name__


def _decode_keeps_state(eng):
    decode = eng._decode
    eng._decode = lambda p, t, s: (decode(p, t, s)[0], s)


def _decode_alters_token(eng):
    decode = eng._decode

    def altered(p, t, s):
        logits, s2 = decode(p, t, s)
        return jax.numpy.roll(logits, 1, axis=-1), s2

    eng._decode = altered


def _decode_half_batch(eng):
    """The decode step's result kept for the second half of the slots
    alone; the first half, where the engine admits first, take theirs."""
    decode = eng._decode

    def half(p, t, s):
        logits, s2 = decode(p, t, s)
        n = logits.shape[0] // 2
        return logits.at[:n].set(logits[n:2 * n]), s2

    eng._decode = half


@pytest.mark.parametrize("fault", [_decode_keeps_state,
                                   _decode_alters_token,
                                   _decode_half_batch])
def test_serve_faults_are_not_correct(fault):
    assert not _serve(fault=fault).correct()
