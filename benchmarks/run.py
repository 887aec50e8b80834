"""The benchmark's one command:

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One cell per process; JAX is first touched in it.  A run that finds no TPU,
or fewer chips than the cell asks for, exits non-zero and prints no result.
`--rehearse-cpu` (never the default) runs the same code at toy size on the
CPU and says `"platform": "cpu"` in its result: a rehearsal, no measurement.
The last line of standard output is the result; the lines before it are
notes (`{"note": ...}`).  Each number `correct` compared stands beside its
limit under the result's last key, `compared`, and on the last lines of
standard error.  See benchmarks/lib/harness.py.
"""

import time

T0 = time.perf_counter()  # set-up is counted from here

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.lib import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], T0))
