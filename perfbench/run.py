"""``python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``

One run of one cell, in this process, on the machine it is started on. The
last line of standard output is the result; the numbers compared, each beside
its limit, are the last lines of standard error. Exits non-zero, with no
result, where jax finds no TPU (or fewer chips than the cell asks for), and
away from the repository (the system under test is imported from it).
"""

import time

T_PROCESS = time.perf_counter()  # set-up is counted from here

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if __name__ == "__main__":
    import omldm_tpu  # noqa: F401  (fails here, with no result, away from the repo)

    from perfbench import harness

    sys.exit(harness.main(sys.argv[1:], T_PROCESS))
