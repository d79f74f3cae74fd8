"""The program under test, as the benchmark sees it: its config registry,
checked against the sizes the benchmark's own config file states."""
from __future__ import annotations

from typing import Any, Dict

from bench.harness import SpecError


def config(c: Dict[str, Any]):
    """The program's config of ``c["program"]["arch"]``; a size that
    differs from the benchmark's file is an error, not a silent change."""
    from repro.configs import get_config
    cfg = get_config(c["program"]["arch"])
    for key, field in c["program"]["fields"].items():
        if getattr(cfg, field) != c[key]:
            raise SpecError(
                f"{cfg.name}: program has {field}={getattr(cfg, field)!r}, "
                f"the benchmark's config states {key}={c[key]!r}")
    return cfg
