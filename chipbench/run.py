"""Run one benchmark cell once, from the root of a checkout:

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result object; the numbers of the
correctness check are the last lines of standard error.  A machine without
the TPU chips the cell asks for exits non-zero and prints no result.
"""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main())
