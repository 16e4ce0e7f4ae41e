"""The comparison of ``compare.py`` against a reference computed in a
lower precision than the configuration states: the control that has to
come out as not correct (PERF.md section 2). Same arguments as
``compare.py`` after the control's name; the reference named has to take
the control's keyword (``solar_open2.logprobs`` does):

    python3 chipbench/reference/control.py bf16_state --model-dir ... \\
        --reference solar_open2 --engine-seed N --tp 1 --probe probe.json

A run of ``chipbench/run.py`` leaves ``model/`` and ``probe.json`` in
``chipbench/.work/<cell>/``.
"""

from __future__ import annotations

import functools
import importlib
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

CONTROLS = {"bf16_state": "state_dtype", "bf16_router": "router_dtype"}


def main() -> int:
    control = sys.argv.pop(1)
    name = sys.argv[sys.argv.index("--reference") + 1]
    from chipbench.reference import compare

    ref = importlib.import_module(f"chipbench.reference.{name}")
    ref.logprobs = functools.partial(
        ref.logprobs, **{CONTROLS[control]: "bfloat16"})
    return compare.main()


if __name__ == "__main__":
    sys.exit(main())
