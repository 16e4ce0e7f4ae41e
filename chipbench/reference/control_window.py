"""The comparison of ``compare.py`` against a reference whose window
layers see EVERY row: the control of what a stack with a window that binds
adds, which has to come out as not correct (PERF.md section 2). Same
arguments as ``compare.py``; the reference named has to take
``ignore_window`` (``afmoe.logprobs`` does):

    python3 chipbench/reference/control_window.py --model-dir ... \\
        --reference afmoe --engine-seed N --tp 1 --probe probe.json

A run of ``chipbench/run.py`` leaves ``model/`` and ``probe.json`` in
``chipbench/.work/<cell>/``. ``control.py`` is the control of the
precision; this one of the mechanism: a program that let its window layers
see the whole context would agree with THIS reference.
"""

from __future__ import annotations

import functools
import importlib
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    name = sys.argv[sys.argv.index("--reference") + 1]
    from chipbench.reference import compare

    ref = importlib.import_module(f"chipbench.reference.{name}")
    ref.logprobs = functools.partial(ref.logprobs, ignore_window=True)
    return compare.main()


if __name__ == "__main__":
    sys.exit(main())
