"""``gc_pause_share``, read the same way, for the cells that report
``tpot_p95_ms``: a collection stalls the reactor and the workers at
once, which shows in the decode steps' tail, so there it moves
``tpot_p95_ms``."""

import pathlib
import runpy

read = runpy.run_path(
    str(pathlib.Path(__file__).with_name("gc_pause_share.py")))["read"]
