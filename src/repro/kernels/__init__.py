"""Pallas TPU kernels for the workloads' compute hot-spots.

The paper itself is an infrastructure/scheduling contribution (no kernel of
its own); these kernels are the perf-critical layers of the *workloads* the
scheduler manages, exercised by the roofline/perf iterations:

  flash_attention   train/prefill attention (causal + sliding-window + GQA)
  decode_attention  flash-decode against ring-buffered KV caches
  rglru_scan        RG-LRU linear recurrence (recurrentgemma)
  wkv6              RWKV6 data-dependent-decay recurrence

Each kernel has a pure-jnp oracle in ``ref.py`` and a jitted dispatcher in
``ops.py``.  The CPU tests sweep shapes/dtypes against the oracles with
``interpret=True``; ``tests/test_chip_compile.py`` compiles each kernel for a
TPU v5e at real widths, and ``chip_smoke.py`` runs them on the chip.
"""
