#!/usr/bin/env python3
"""Run a cell of the expert-share family once, as ``benchmark/run.py``
does, and read the emitted tokens against the plain reference's three
planted controls too (``reference_longcat_flash.CONTROLS``: weights
rounded to int8, a held expert dropped, the identity term dropped):
one ``control`` note line each, with the rows off, the worst row, the
expert sublayer's worst row (every layer, at the decode step's rows)
and whether the cell's three limits refuse it.  What the limits of
``benchmark/drivers/serve_scmoe.py`` were set from (PERF.md section 6,
PR 39).  One ``moe_combine`` note line more: the rows the expert
layer's combine walked in the window's decode steps, of its output's
rows (``moe_combine_rows`` / ``moe_output_rows``).  Takes ``run.py``'s
arguments:

    python3 tools/scmoe_controls.py --workload serve-longcat-omni-reason-zipf \\
        --seed <n> --seconds 51 --trace 0
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import run                               # noqa: E402
from benchmark.drivers import serve_scmoe               # noqa: E402
from paddle_tpu.testing import reference_longcat_flash  # noqa: E402

COMBINE = ("moe_combine_rows", "moe_output_rows")


def run_noting_the_combine(ctx, driver=serve_scmoe.run):
    record = driver(ctx)
    walked, rows = (record["moe"][k] for k in COMBINE)
    ctx.note(phase="moe_combine", moe_combine_rows=walked,
             moe_output_rows=rows,
             moe_combine_share=walked / rows if rows else None)
    return record


if __name__ == "__main__":
    serve_scmoe.CONTROLS = reference_longcat_flash.CONTROLS
    serve_scmoe.MOE_COUNTERS += COMBINE
    serve_scmoe.run = run_noting_the_combine
    run.main(sys.argv[1:])
