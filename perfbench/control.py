"""The control of ``correct``: one run of a cell as ``perfbench.run`` makes
it, plus the plain reference once more in float8 (e4m3), the precision below
the bfloat16 products both configurations state, put in the program's place.

``python3 -m perfbench.control --workload <name> --seed <n> --seconds <s> --trace 0``

The info line's ``control`` holds the numbers the float8 pass gives for the
cell's comparisons; at least one of them has to lie over its limit (PERF.md,
section 2, lists the readings).  The benchmark's own runs never do this.
"""

import sys

from . import run

if __name__ == "__main__":
    run.CONTROL_PRECISION = "float8"
    sys.exit(run.main())
