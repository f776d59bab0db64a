#!/usr/bin/env python3
"""The two readings behind the limits of ``perf/reference/moonlight.py``'s
``check_greedy``, taken on the chip THROUGH THE SERVER at the cell's load.

    chiprun --chips 1 -- python3 perf/tools/moonlight_limits.py \\
        --seeds 5001 5002 [--seconds 10] [--weights float8 bfloat16]

For the builder (PERF.md section 6, PR 38), not a cell. It is
``perf/tools/mellum_limits.py`` (read there what the two children do and
why) pointed at ``serve-moonlight-16b-reason``: ``bfloat16`` is the cell as
it is; ``float8`` serves the same weights rounded to e4m3's three bits of
mantissa, the nearest precision below the configuration's, and has to come
out as not correct. The children are started from THIS file, so that they
too run this cell."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perf.tools import mellum_limits  # noqa: E402

mellum_limits.WORKLOAD = "serve-moonlight-16b-reason"
mellum_limits.__file__ = os.path.abspath(__file__)

if __name__ == "__main__":
    sys.exit(mellum_limits.main())
