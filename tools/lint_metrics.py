#!/usr/bin/env python
"""Metric-name lint (the FLAGS-lint idiom applied to telemetry): every
metric registered in ``observability.default_registry()`` — native
families AND collector-declared ones — must be

- snake_case (``[a-z][a-z0-9_]*``),
- unique (the registry enforces this at registration; the lint
  re-checks so a poisoned catalog list is caught in tests),
- unit-suffixed with one of ``observability.metrics.UNIT_SUFFIXES``
  (``_total``/``_ms``/``_bytes``/``_ratio``/``_state``/``_count``/
  ``_value``),
- present in the README "Observability" metric catalog table (a metric
  nobody documented is a metric nobody will find in a dashboard).

Usage: python tools/lint_metrics.py        (exit 1 on any finding)
Also runs as a tier-1 test (tests/test_tools_gates.py).
"""
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

README = os.path.join(REPO, "README.md")

_SNAKE = re.compile(r"^[a-z][a-z0-9_]*$")


def check(names, readme_text, suffixes=None):
    """-> list of error strings (empty = clean)."""
    if suffixes is None:
        from paddle_tpu.observability.metrics import UNIT_SUFFIXES
        suffixes = UNIT_SUFFIXES
    errors = []
    seen = set()
    for name in names:
        if name in seen:
            errors.append(f"metric {name!r} registered more than once")
        seen.add(name)
        if not _SNAKE.match(name):
            errors.append(f"metric {name!r} is not snake_case")
        if not name.endswith(tuple(suffixes)):
            errors.append(
                f"metric {name!r} lacks a unit suffix "
                f"({', '.join(suffixes)})")
        # catalog rows render the name in backticks: `name`
        if f"`{name}`" not in readme_text:
            errors.append(
                f"metric {name!r} is missing from the README "
                f"\"Observability\" metric catalog")
    return errors


def registered_names():
    """Import every metric-bearing subsystem, then read the registry's
    catalog (native + collector-declared families)."""
    import paddle_tpu  # noqa: F401 — executor/passes/resilience register
    import paddle_tpu.serving  # noqa: F401 — ServingStats bridge
    import paddle_tpu.train  # noqa: F401 — train supervisor families
    import paddle_tpu.models.generation  # noqa: F401 — decode stages
    import paddle_tpu.kernels  # noqa: F401 — attention impl resolution
    from paddle_tpu.observability import default_registry
    return sorted(default_registry().catalog())


def main():
    names = registered_names()
    with open(README, encoding="utf-8") as f:
        readme = f.read()
    errors = check(names, readme)
    if errors:
        print("METRIC LINT ERRORS:")
        for e in errors:
            print(" -", e)
        return 1
    print(f"metrics clean: {len(names)} registered names, all "
          f"snake_case, unit-suffixed and documented in the README "
          f"catalog")
    return 0


if __name__ == "__main__":
    sys.exit(main())
