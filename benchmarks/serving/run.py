"""Serving benchmark: one run of one cell, in one process.

    python3 benchmarks/serving/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine that holds the chips the
cell asks for.  The last line of standard output is the result, one JSON
object; the numbers compared with the reference are the last lines of
standard error.  Exits non-zero and prints no result when JAX finds no
TPU or fewer chips than the cell needs.  JAX's compilation cache is kept
in ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
CACHE_DIR = ROOT / ".jax_cache"
sys.path[:0] = [str(HERE), str(ROOT / "src")]


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def use_compile_cache() -> None:
    """Every program, however quick to compile, in the checkout's cache."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def check_chips(chips: int) -> None:
    """Exit non-zero unless JAX holds at least ``chips`` TPU chips."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"benchmark: needs a TPU; JAX found platform "
                 f"{devs[0].platform!r} ({len(devs)} device(s))")
    if len(devs) < chips:
        sys.exit(f"benchmark: the cell needs {chips} chips; JAX found "
                 f"{len(devs)}")


def main(argv=None) -> int:
    args = parse(argv)
    if not (ROOT / "src" / "repro").is_dir():
        sys.exit(f"benchmark: no program under test at {ROOT / 'src/repro'}")
    import harness
    cell = harness.load_cell(args.workload)
    check_chips(cell.chips)
    use_compile_cache()
    res = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           T_START)
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
