#!/usr/bin/env python3
"""One run of one benchmark cell:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result (one JSON object);
earlier lines are JSON too and say how the run went. See harness/bench.py.
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
# the benchmark's own modules, then the program's (wire types and clients)
sys.path[:0] = [HERE, os.path.dirname(HERE)]

if __name__ == "__main__":
    from harness.bench import main

    sys.exit(main())
