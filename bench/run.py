"""Run one cell of the on-chip benchmark.

  python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout. Without a TPU, or with fewer chips than
the cell asks for, it exits non-zero and prints no result. JAX's
persistent compilation cache lives in ``.jax_cache/`` at the root of the
checkout, so only the first run of a cell there compiles.
"""
import time

T_START = time.monotonic()

import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    # The script's own directory leaves the path: its modules are imported
    # as ``bench.*`` and must not shadow top-level names.
    sys.path[:1] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness

    return harness.main(sys.argv[1:], T_START)


if __name__ == "__main__":
    sys.exit(main())
