"""``mfu.prefill``, read the same way, for the cells that report
``tpot_p95_ms``: what it reads shows in the decode steps' tail, as a
prefill, a plan change or a compile holds the chip or the reactor while
decode steps wait, so there it moves ``tpot_p95_ms``."""

import pathlib
import runpy

read = runpy.run_path(
    str(pathlib.Path(__file__).with_name("mfu.prefill.py")))["read"]
