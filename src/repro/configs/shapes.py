"""The assigned input shapes, which ``core.meshplan.plan_job`` plans for.

  train_4k     train step (fwd + bwd + optimizer)
  prefill_32k  prefill (fwd, writes the KV cache)
  decode_32k   decode (1 new token against a seq_len KV cache)
  long_500k    decode at a 512k context
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    name: str
    kind: str            # train | prefill | decode
    seq_len: int
    global_batch: int


TRAIN_4K = ShapeSpec("train_4k", "train", 4096, 256)
PREFILL_32K = ShapeSpec("prefill_32k", "prefill", 32_768, 32)
DECODE_32K = ShapeSpec("decode_32k", "decode", 32_768, 128)
LONG_500K = ShapeSpec("long_500k", "decode", 524_288, 1)

SHAPES = {s.name: s for s in (TRAIN_4K, PREFILL_32K, DECODE_32K, LONG_500K)}
