"""Records the small profiler trace that ``test_devtrace.py`` reduces: one
traced step of ``riot21.steady`` at a small batch (events per source per
step), on the chip: the scans' per-iteration ops make a full-size step
about 50 MB. The test reads it gzip-compressed.

    python bench/tests/record_trace.py riot21_b8.xplane.pb 8
    gzip -9 -c riot21_b8.xplane.pb > bench/data/riot21_b8.xplane.pb.gz
"""
import os
import sys
import time

T_START = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)


def main() -> int:
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, BENCH)
    import jax

    from lib.cell import load_cell
    from lib.harness import execute

    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 1
    cell = load_cell("riot21.steady", ROOT)
    result = execute(cell, 7, 3.0, True, T_START, jax.devices()[:1], batch=int(sys.argv[2]),
                     keep_trace=sys.argv[1])
    print(result["device"], result["breakdown"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
