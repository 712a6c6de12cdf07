"""The chip benchmark's command: one run of one cell of ``BENCHMARK.json``.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

See ``chipbench/harness.py`` for what a run does.  Nothing but the
standard library is imported before ``main``: the reference's spawn-started
worker processes import this file again and must not import jax.
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))
sys.path.insert(0, HERE)

if __name__ == "__main__":
    from chipbench.harness import main
    sys.exit(main(t_start=T_START))
