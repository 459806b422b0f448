"""Run one benchmark cell once, on the accelerator this machine holds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints one JSON line of run details, then the result as the last line of
standard output; the numbers compared with the reference, each beside its
limit, are the last lines of standard error.  Without an accelerator, or
with fewer chips than the cell asks for, it exits non-zero and prints no
result.  JAX's compilation cache is kept in ``.jax_cache`` at the root of
the checkout.
"""

import os
import sys
import time


def main() -> int:
    t_start = time.perf_counter()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [root, os.path.join(root, "src")]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(root, ".jax_cache")
    from bench.harness import main as harness_main

    return harness_main(sys.argv[1:], t_start=t_start)


if __name__ == "__main__":
    sys.exit(main())
